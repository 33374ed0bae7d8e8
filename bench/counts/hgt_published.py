"""Work counts of one full-graph step of HGT as published
(``bench/reference/hgt_published.py``): per layer of attention width d
(the layer's input), output width n and H heads of dk = d / H,

* node-typed GEMMs over all N nodes: K, Q, V (d -> d) and the A-Linear
  (d -> n);
* per-head relation GEMMs over the U unique (src, relation) pairs:
  ``katt`` and ``msg``, each 2·U·H·dk² FLOPs;
* per-head scores over the E edges (a d-wide dot, the prior and the scale),
  edge softmax and the attention-weighted sum of messages;
* exact GELU and, where d == n, the gated skip over the nodes;

relu between layers and cross-entropy on every node.

``segment_mm`` counts the typed GEMMs the GEMM template runs (node-typed
and per-head): forward and, when training, dW always and dX where the
input depends on parameters, so not for layer 0's K, Q, V. ``traversal``
counts what the traversal kernels compute, forward: the softmax statistics
and the weighted sum, H columns; the scores are XLA's.
"""
from __future__ import annotations

from typing import Dict, Sequence

F32 = 4
HEADS = 8


def _zero() -> Dict[str, float]:
    return {"flops": 0.0, "bytes": 0.0}


def _add(acc: Dict[str, float], flops: float, nbytes: float) -> None:
    acc["flops"] += float(flops)
    acc["bytes"] += float(nbytes)


def step(stats: Dict[str, int], dims: Sequence[int], graph: dict,
         train: bool = True, heads: int = HEADS) -> Dict[str, object]:
    """``stats``: ``work.graph_stats``; ``graph``: the configuration's
    ``graph`` entry (its ``num_etypes`` and ``num_ntypes`` are used)."""
    E, U, Nd, N = (stats[k] for k in ("E", "U", "Nd", "N"))
    R, T, H = graph["num_etypes"], graph["num_ntypes"], heads
    gemm, trav = _zero(), _zero()
    model_flops = 0.0
    for layer, (d, n) in enumerate(zip(dims[:-1], dims[1:])):
        dk = d // H
        kqv = 2.0 * 3 * N * d * d
        rel = 2.0 * 2 * U * H * dk * dk             # katt, msg
        a_lin = 2.0 * N * d * n
        fwd = kqv + rel + a_lin
        w_bytes = 3 * T * d * d + 2 * R * H * dk * dk + T * d * n
        # x read; weights; k, q, v, katt, msg, gelu(agg) and o
        _add(gemm, fwd, F32 * (N * d + w_bytes + 3 * N * d + 2 * U * d
                                + N * d + N * n))
        # softmax statistics and weighted sum, H columns
        soft_agg = 3.0 * E * H + 2.0 * E * d
        _add(trav, soft_agg, F32 * (E * H + U * d + Nd * d))
        scores = 2.0 * E * d + 2.0 * E * H           # dots, prior, scale
        skip = 3.0 * N * d if d == n else 0.0
        model_flops += fwd + scores + soft_agg + skip
        if train:
            dx = (kqv if layer > 0 else 0.0) + rel + a_lin
            # dY rows in, dW out, dX rows out
            bwd_bytes = F32 * (3 * N * d + 2 * U * d + N * n + w_bytes
                               + 2 * U * d + N * d
                               + (N * d if layer > 0 else 0))
            _add(gemm, fwd + dx, bwd_bytes)
            # scores: dkatt, dq; aggregation: dmsg, datt; softmax; prior
            edges = 8.0 * E * d + 6.0 * E * H
            model_flops += fwd + dx + edges + 2.0 * skip
    c = dims[-1]
    model_flops += (3.0 if train else 1.0) * 5.0 * N * c    # softmax xent
    return {"segment_mm": gemm, "traversal": trav,
            "model_flops": model_flops}
