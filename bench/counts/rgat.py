"""Work counts of one full-graph RGAT step (Hector's RGAT: per-relation
W_r, attention vectors w_s[r], w_t[r], edge softmax over each destination's
in-edges, attention-weighted sum; relu between layers; cross-entropy on
every node).

``segment_mm`` counts the per-relation GEMMs: forward, and when training
dW always and dX only where the layer input depends on parameters, so not
for layer 0's features.
"""
from __future__ import annotations

from typing import Dict, Sequence

F32 = 4


def _zero() -> Dict[str, float]:
    return {"flops": 0.0, "bytes": 0.0}


def _add(acc: Dict[str, float], flops: float, nbytes: float) -> None:
    acc["flops"] += float(flops)
    acc["bytes"] += float(nbytes)


def step(stats: Dict[str, int], dims: Sequence[int], graph: dict,
         train: bool = True) -> Dict[str, object]:
    """``stats``: ``work.graph_stats``; ``graph``: the configuration's
    ``graph`` entry (its ``num_etypes`` is used)."""
    E, U, D, Nd, Nsd, N = (stats[k] for k in ("E", "U", "D", "Nd", "Nsd",
                                              "N"))
    R = graph["num_etypes"]
    gemm, trav = _zero(), _zero()
    model_flops = 0.0
    for layer, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        # forward GEMMs: hs = x_u W_r on unique (u, r); the two attention
        # projections x_u (W_r w_s[r]) and x_v (W_r w_t[r]) as k -> 1 GEMMs
        fwd = 2.0 * (U * k * n + U * k + D * k)
        wprod = 2.0 * 2 * R * k * n          # W_r w_s[r], W_r w_t[r]
        fwd_bytes = F32 * (Nsd * k + R * k * n + 2 * R * k
                           + U * n + U + D)
        _add(gemm, fwd, fwd_bytes)
        # traversal: softmax statistics and the weighted sum of messages
        agg = 2.0 * E * n + 3.0 * E
        _add(trav, agg, F32 * (E + U * n + Nd * n))
        model_flops += fwd + wprod + agg + 2.0 * E + N * n
        if train:
            # dW: x^T dY per relation; dX = dY W^T where x depends on params
            dw = fwd
            dx = fwd if layer > 0 else 0.0
            bwd_bytes = F32 * ((U * n + U + D)            # dY
                               + R * k * n + 2 * R * k)   # dW
            if layer > 0:
                bwd_bytes += F32 * Nsd * k                # dX rows
            _add(gemm, dw + dx, bwd_bytes)
            # aggregation backward (dmsg, datt) and softmax backward
            model_flops += dw + dx + wprod + 4.0 * E * n + 4.0 * E
    c = dims[-1]
    model_flops += (3.0 if train else 1.0) * 5.0 * N * c    # softmax xent
    return {"segment_mm": gemm, "traversal": trav,
            "model_flops": model_flops}
