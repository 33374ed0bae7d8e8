"""Work counts of one full-graph single-head HGT step (a test fixture: the
data-driven test drops it into a checkout as ``bench/counts/hgt.py``).

``segment_mm``: the node-typed K/Q/V linears over all ``N`` nodes and the
per-relation W_att, W_msg GEMMs over the unique (src, relation) pairs
``U``; when training, dW of each and dX where the input depends on
parameters. ``traversal``: the edge dot products, edge softmax and the
weighted sum, forward.
"""
from __future__ import annotations

from typing import Dict, Sequence

F32 = 4


def step(stats: Dict[str, int], dims: Sequence[int], graph: dict,
         train: bool = True) -> Dict[str, object]:
    E, U, Nd, N = (stats[k] for k in ("E", "U", "Nd", "N"))
    R, T = graph["num_etypes"], graph["num_ntypes"]
    gemm = {"flops": 0.0, "bytes": 0.0}
    trav = {"flops": 0.0, "bytes": 0.0}
    model_flops = 0.0
    for layer, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        typed = 2.0 * 3 * N * k * n                  # K, Q, V
        edge = 2.0 * 2 * U * n * n                   # katt, msg
        gemm["flops"] += typed + edge
        gemm["bytes"] += F32 * (N * k + 3 * T * k * n + 3 * N * n
                                + 2 * R * n * n + 2 * U * n)
        agg = 2.0 * E * n + 3.0 * E + 2.0 * E * n    # dot, softmax, sum
        trav["flops"] += agg
        trav["bytes"] += F32 * (2 * U * n + N * n + E + Nd * n)
        model_flops += typed + edge + agg
        if train:
            bwd = typed * (2 if layer > 0 else 1) + 2 * edge
            gemm["flops"] += bwd
            gemm["bytes"] += F32 * (3 * N * n + 3 * T * k * n
                                    + 2 * U * n + 2 * R * n * n)
            model_flops += bwd + 2 * agg
    model_flops += (3.0 if train else 1.0) * 5.0 * N * dims[-1]
    return {"segment_mm": gemm, "traversal": trav,
            "model_flops": model_flops}
