"""The data-driven harness behind ``bench/run.py``.

Everything is found by name from ``BENCHMARK.json``:

* a cell (``workloads[]``) names a configuration and a traffic mix;
* the configuration's ``file`` (under ``bench/configs/``) holds the model,
  the graph's statistics, the ``hector.compile`` arguments, the precision
  and the optimizer;
* the traffic mix is ``bench/traffic/<traffic>.json``: parameters, and the
  ``runner`` (``bench/runners/<runner>.py``) that runs such a mix;
* the cell's correctness limits are ``bench/limits/<cell>.json``;
* each per-layer metric is read by ``bench/metrics/<metric>.py``;
* the configuration's ``reference`` key names the model's plain reference,
  ``bench/reference/<reference>.py``, and its work counts,
  ``bench/counts/<reference>.py``.

A new configuration, mix, cell or metric is new files and entries; no
existing file changes. A new model brings exactly these files:

* ``bench/reference/<reference>.py``: ``param_shapes(dims, num_etypes,
  num_ntypes)`` and ``layer(p, x, dg, num_nodes, chunk, precision)``
  (``bench/reference/stack.py``);
* ``bench/counts/<reference>.py``: ``step(stats, dims, graph, train)``
  (``bench/work.py``);
* ``bench/configs/<config>.json`` and ``bench/limits/<cell>.json``;

and entries in ``BENCHMARK.json``: the configuration, the cell, and the
cell's name in the ``workloads`` of each metric it reports.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``value <= limit`` passes."""

    name: str
    value: float
    limit: float

    @property
    def passed(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a runner hands back to the harness."""

    attempted: int
    failed: int
    metrics: Dict[str, float]          # end-to-end metrics by name
    checks: List[Check]
    memory_peak_bytes: int
    layer: Dict[str, Any]              # what the per-layer readers read
    window_compiles: int = 0


@dataclasses.dataclass
class Cell:
    """One run of one cell: its entries, files and the run's arguments."""

    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    root: pathlib.Path
    t_start: float
    cache_dir: Optional[pathlib.Path] = None

    @property
    def trace_dir(self) -> pathlib.Path:
        return self.cache_dir / "trace" / self.name

    def limit(self, name: str) -> float:
        return float(self.limits[name]["limit"])

    @contextlib.contextmanager
    def window(self):
        """The measured window: profiled when ``trace`` is on, and marked
        with a ``bench.window`` annotation either way."""
        import jax
        if not self.trace:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


def load_cell(name: str, *, seed: int, seconds: float, trace: bool,
              root: pathlib.Path = ROOT, t_start: Optional[float] = None,
              bench: Optional[dict] = None, **overrides) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(root / "bench" / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    overrides.setdefault("cache_dir", root / "bench" / ".cache")
    return Cell(name=name, config=config, traffic=traffic, limits=limits,
                chips=int(w["chips"]), seed=int(seed),
                seconds=float(seconds), trace=bool(trace), root=root,
                t_start=time.perf_counter() if t_start is None else t_start,
                **overrides)


def runner(cell: Cell):
    return importlib.import_module(f"bench.runners.{cell.traffic['runner']}")


@functools.lru_cache(maxsize=None)
def module(kind: str, name: str, root: pathlib.Path = ROOT):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``, loaded once;
    a missing file is an error that names it."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"bench: {name!r} needs the file bench/{kind}/{name}.py, which "
            f"{root} does not have")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(data) -> float | None``."""
    return module("metrics", name, root).read


# ---------------------------------------------------------------------------
# the machine
# ---------------------------------------------------------------------------
def require_devices(chips: int):
    """The devices to run on; exits, printing no result, without a TPU or
    with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX finds "
                 f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(cache_dir: pathlib.Path) -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at a fixed path inside the checkout; every program is
    cached, however fast it compiled, so later runs compile nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        cache_dir / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts traces and backend compiles reported by ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def seed_int(seed: int, *tags: int) -> int:
    """A 31-bit integer drawn from the run's seed (any size) and tags."""
    import numpy as np
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *tags])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def per_layer(bench: dict, cell: Cell, data: Dict[str, Any]
              ) -> Dict[str, dict]:
    out = {}
    for m in bench["per_layer"]:
        if not _applies(m, cell.name):
            continue
        value = metric_reader(m["name"], cell.root)(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(bench: dict, cell: Cell, outcome: Outcome, devices,
                trace=None) -> dict:
    """The run's last line; ``trace`` is the reduced device trace of a
    ``--trace 1`` run."""
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": all(c.passed for c in outcome.checks)
            and bool(outcome.checks),
            "attempted": outcome.attempted, "failed": outcome.failed}
    if trace is not None:
        line["metrics"] = per_layer(bench, cell,
                                    dict(outcome.layer, trace=trace))
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        line["device"] = device
        line["breakdown"] = {"device_ops": trace.top_ops(10),
                             "idle_gaps": trace.top_gaps(10)}
    else:
        line["metrics"] = {
            m["name"]: {"value": outcome.metrics[m["name"]],
                        "unit": units[m["name"]]}
            for m in bench["end_to_end"] if _applies(m, cell.name)}
        line["device"] = device
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def main(argv=None, t_start: Optional[float] = None,
         root: pathlib.Path = ROOT) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark(root)
    cell = load_cell(args.workload, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), root=root, t_start=t_start,
                     bench=bench)
    devices = require_devices(cell.chips)
    import jax
    cache = enable_compile_cache(cell.cache_dir)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"])
    dev = devices[0]
    log(f"[device] platform {dev.platform}; kind {dev.device_kind}; count "
        f"{len(devices)}; jax {jax.__version__}; compile cache {cache}; "
        f"matmul precision {cell.config['precision']}")
    counter = CompileCounter()
    outcome = runner(cell).run(cell, devices, counter)
    log(f"[window] compiles inside the window: {outcome.window_compiles}")
    trace = None
    if cell.trace:
        from bench import trace_reduce
        trace = trace_reduce.load(str(cell.trace_dir))
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
    counter.close()
    line = result_line(bench, cell, outcome, devices, trace)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.passed else 'FAILED'}", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return 0
