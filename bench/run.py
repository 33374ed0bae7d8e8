#!/usr/bin/env python3
"""Run one benchmark cell on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Loads the cell named in ``BENCHMARK.json``, sets it up (graph, compile,
inputs from ``--seed``, warm-up of the cell's shapes), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics (from a
profiler trace of the window) with ``--trace 1``. Exits non-zero, printing
no result, without a TPU or with fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t_start=T_START, root=_ROOT))
