"""Work counts: the operations and the least bytes a step needs.

Counted from the model's equations and the graph's statistics alone, so a
count is the same whichever kernel, materialization or backend does the
work. Bytes are the least any implementation must move: each distinct
tensor a kernel family reads counted once per step, plus what it writes,
float32 throughout. So a kernel family's share of its roofline stays at or
under 100%.

Two kernel families are counted, matching the program's Pallas kernels:

* ``segment_mm``: the typed (per-relation) GEMMs of the GEMM template,
  forward and, when training, the backward GEMMs (dW always; dX only where
  the layer input depends on parameters, so not for layer 0's features);
* ``traversal``: edge softmax and aggregation over the destination CSR,
  forward only (their backward runs as plain XLA ops in the program).

``model_flops`` counts the whole step, forward and backward, for MFU.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

F32 = 4


def graph_stats(src: np.ndarray, dst: np.ndarray, etype: np.ndarray,
                num_nodes: int) -> Dict[str, int]:
    """Counts the work depends on: edges ``E``, unique (src, etype) pairs
    ``U``, unique (dst, etype) pairs ``D``, distinct destinations ``Nd``,
    distinct nodes at either end ``Nsd``, and ``N``."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    et = np.asarray(etype, np.int64)
    return {
        "E": int(src.size),
        "U": int(np.unique(et * num_nodes + src).size),
        "D": int(np.unique(et * num_nodes + dst).size),
        "Nd": int(np.unique(dst).size),
        "Nsd": int(np.unique(np.concatenate([src, dst])).size),
        "N": int(num_nodes),
    }


def _zero() -> Dict[str, float]:
    return {"flops": 0.0, "bytes": 0.0}


def _add(acc: Dict[str, float], flops: float, nbytes: float) -> None:
    acc["flops"] += float(flops)
    acc["bytes"] += float(nbytes)


def rgat_step(stats: Dict[str, int], dims: Sequence[int], num_etypes: int,
              train: bool = True) -> Dict[str, object]:
    """One full-graph RGAT step (Hector's RGAT: per-relation W_r, attention
    vectors w_s[r], w_t[r], edge softmax over each destination's in-edges,
    attention-weighted sum; relu between layers; cross-entropy on every
    node)."""
    E, U, D, Nd, Nsd, N = (stats[k] for k in ("E", "U", "D", "Nd", "Nsd",
                                              "N"))
    R = num_etypes
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


# step counts by the configuration's ``reference`` model
STEP_WORK = {"rgat": rgat_step}


def least_seconds(family: Dict[str, float], peaks: dict):
    """The least time the chip could take for a family's work: the larger
    of operations over peak FLOP/s and bytes over HBM bandwidth. Returns
    ``(seconds, "compute" | "memory")``."""
    t_flops = family["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = family["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")


def roofline_share(data: dict, family: str, patterns) -> "float | None":
    """A kernel family's share of its roofline over a traced window, in %:
    the least time of the window's steps over the family's device time.
    None where the trace holds none of the family's kernels."""
    from bench import peaks
    trace, w = data["trace"], data.get("work")
    seconds = trace.family_seconds(patterns)
    if w is None or seconds <= 0:
        return None
    least, _ = least_seconds(w[family], peaks.peaks_for(data["device_kind"]))
    return 100.0 * least * data["steps"] / seconds
