"""Tiny versions of the benchmark's cells for CPU tests: the same runners,
configurations and mixes, with the graph, tiles and traffic shrunk and the
Pallas kernels in interpret mode."""
from __future__ import annotations

import pathlib

import jax

from bench import harness  # noqa: F401 (tests reach it as tiny.harness)

SEED = 2**33 + 12345          # wider than 32 bits, as command-line seeds may be


# A cell whose files stay under bench/ while BENCHMARK.json leaves it out
# until its traffic is measured again on the chip; the tests still drive it.
PENDING = {"configs": [{"name": "rgcn-mag",
                        "file": "bench/configs/rgcn-mag.json"}],
           "workloads": [{"name": "rgcn-mag.serve_poisson",
                          "config": "rgcn-mag", "traffic": "serve_poisson",
                          "chips": 1}]}


def benchmark(root: pathlib.Path = harness.ROOT) -> dict:
    """``BENCHMARK.json`` with the pending cells added."""
    bench = harness.load_benchmark(root)
    for key, entries in PENDING.items():
        names = {e["name"] for e in bench[key]}
        bench[key] += [e for e in entries if e["name"] not in names]
    return bench


def cell(name: str, tmp_path: pathlib.Path, seed: int = SEED,
         root: pathlib.Path = harness.ROOT) -> harness.Cell:
    c = harness.load_cell(name, seed=seed, seconds=0.5, trace=False,
                          root=root, bench=benchmark(root),
                          cache_dir=tmp_path / "cache")
    c.config["compile"].update(backend="pallas_interpret", tile=8,
                               node_block=8)
    g = c.config["graph"]
    if c.traffic["runner"] == "train_full":
        g.update(num_nodes=400, num_edges=1200, num_etypes=12)
    else:
        g.update(num_nodes=1500, num_edges=9000)
        c.traffic.update(rate_rps=16, max_batch=4, sizes=[1, 2],
                         warm_seconds=0.25, check_requests=4,
                         slo_ms=60_000)
    return c


def run(c: harness.Cell) -> harness.Outcome:
    jax.config.update("jax_default_matmul_precision", "highest")
    counter = harness.CompileCounter()
    try:
        return harness.runner(c).run(c, jax.devices(), counter)
    finally:
        counter.close()


def correct(outcome: harness.Outcome) -> bool:
    return bool(outcome.checks) and all(ch.passed for ch in outcome.checks)
