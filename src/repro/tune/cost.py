"""Cheap cost-model prior used to prune variants before measurement.

The model only needs to *rank* candidates well enough that the top-K always
contains the winner; on-device timing makes the final call. It scores bytes
moved through the memory hierarchy plus a per-grid-step overhead term —
the two effects the tuning knobs actually trade against each other:

* gather fusion removes the materialized ``[rows, k]`` HBM copy but pins the
  whole source block in VMEM and the index maps in SMEM — infeasible past
  either budget (the same gate codegen applies);
* smaller row tiles pay more grid-step overhead (but can win on skewed
  type segments where big tiles are mostly padding);
* the interpret backend exists for correctness only and is effectively
  infinitely expensive.
"""
from __future__ import annotations

from typing import List, Sequence

from repro.tune import device as D
from repro.tune import space as S

_GRID_STEP_COST_BYTES = 2048   # fixed overhead per grid step, in byte units
_INFEASIBLE = 1e9

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8}


def _eff_backend(variant, plan_backend: str) -> str:
    return plan_backend if variant.backend == S.DEFAULT else variant.backend


def score(key: str, variant, plan_backend: str) -> float:
    """Predicted relative cost of running the keyed op with ``variant``."""
    info = S.parse_key(key)
    eff = _eff_backend(variant, plan_backend)
    if eff == "pallas_interpret" and plan_backend != "pallas_interpret":
        return _INFEASIBLE
    itemsize = _ITEMSIZE.get(info["dtype"], 4)

    if info["kind"] == "trav":
        ep, d = info["padded_edges"], info["d"]
        io = ep * d * itemsize                       # message traffic
        if eff != "xla":
            msg_rows = (info["padded_edges"] if not info["compact_msg"]
                        else max(1, info["padded_edges"] // 2))
            fits = D.fused_gather_fits(msg_rows, d, itemsize, ep,
                                       info["edge_tile"])
            fuse = variant.fuse_gather
            if fuse is None:
                fuse = fits
            if fuse:
                if not fits:
                    return _INFEASIBLE
                io = msg_rows * d * itemsize
            else:
                io += ep * d * itemsize              # dst-sorted copy
        return io

    k, n = info["k"], info["n"]
    rp, x_rows = info["padded_rows"], info["x_rows"]
    tr = variant.tile_rows or info["lay_tile"]
    tn = min(variant.tile_n or 128, n)
    io = rp * (k + n) * itemsize                     # X in + Y out
    if eff != "xla" and info["fusable"]:
        fits = D.fused_gather_fits(x_rows, k, itemsize, rp, tr)
        fuse = variant.fuse_gather
        if fuse is None:
            fuse = fits
        if fuse:
            if not fits:
                return _INFEASIBLE
            io = x_rows * k * itemsize + rp * n * itemsize
        else:
            io += rp * k * itemsize                  # materialized copy
    grid_steps = max(1, rp // max(1, tr)) * max(1, n // max(1, tn))
    return io + grid_steps * _GRID_STEP_COST_BYTES


def prune(key: str, candidates: Sequence, plan_backend: str,
          k: int) -> List:
    """Keep the default variant (always, first) plus the cheapest
    alternatives in ascending predicted cost, dropping infeasible ones."""
    default = candidates[0]
    scored = sorted(
        ((score(key, c, plan_backend), i) for i, c in enumerate(candidates)
         if c != default),
        key=lambda t: t[0],
    )
    keep = [candidates[i] for s, i in scored if s < _INFEASIBLE]
    return [default] + keep[: max(0, k - 1)]
