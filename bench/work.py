"""Work counts: the operations and the least bytes a step needs.

Counted from the model's equations and the graph's statistics alone, so a
count is the same whichever kernel, materialization or backend does the
work. Bytes are the least any implementation must move: each distinct
tensor a kernel family reads counted once per step, plus what it writes,
float32 throughout. So a kernel family's share of its roofline stays at or
under 100%.

Each model's counts are a file of their own, ``bench/counts/<reference>.py``
(named by the configuration's ``reference`` key), which provides

    step(stats, dims, graph, train) -> {"segment_mm": {"flops", "bytes"},
                                         "traversal": {"flops", "bytes"},
                                         "model_flops": float}

from ``graph_stats``, the layer widths, the configuration's ``graph``
entry and whether the step trains. The two kernel families match the
program's Pallas kernels:

* ``segment_mm``: the typed GEMMs of the GEMM template, forward and, when
  training, the backward GEMMs;
* ``traversal``: edge softmax and aggregation over the destination CSR,
  forward only (their backward runs as plain XLA ops in the program).

``model_flops`` counts the whole step, forward and backward, for MFU.
This file keeps what every model shares: the graph's statistics, the least
time of a family's work and a family's share of its roofline.
"""
from __future__ import annotations

import pathlib
from typing import Dict

import numpy as np

from bench import harness


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


def step_work(cfg: dict, stats: Dict[str, int], train: bool = True,
              root: pathlib.Path = harness.ROOT) -> Dict[str, object]:
    """One full-graph step's work for a configuration: the ``step`` of
    ``bench/counts/<reference>.py``, found by the configuration's
    ``reference`` key as the harness finds everything else."""
    from bench.runners.common import dims
    counts = harness.module("counts", cfg["reference"], root)
    return counts.step(stats, dims(cfg), cfg["graph"], train)


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
