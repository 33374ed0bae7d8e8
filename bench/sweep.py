#!/usr/bin/env python3
"""One sweep of open-loop rates for a serving cell: the knee, the cell's
rate and its SLO.

    python bench/sweep.py --workload <cell> --rates 25,100,110,...,200 \
        [--seconds 10] [--seed N] [--out FILE] [--write]

Sets the cell's server up once, then serves ``--seconds`` of the cell's
traffic at each rate in turn, in ascending order, with a deadline too long
to reject anything. For each rate it prints the median and 95th percentile
from due time to completion, the completed rate, and whether the queue
grew: the median latency of the window's last third over its first third.

* The knee is the highest rate below the first rate that is not steady
  (a growth of ``GROWTH_LIMIT`` or more, or a request that never
  completed). The sweep stops after two rates in a row that are not.
* The cell's rate is ``RATE_SHARE`` of the knee, rounded.
* The SLO is a multiple of the lowest rate's median (the low-load p50):
  the smallest of ``SLO_MULTIPLES`` at which a window at the cell's
  rate, served by a fresh runtime with that SLO (calibrated and warmed as
  the cell does it), misses no request and compiles nothing. Each such
  probe is printed, the degenerate ones too.

``--write`` writes the rate and the SLO into the cell's traffic file. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

NO_DEADLINE_MS = 600_000.0
GROWTH_LIMIT = 1.25
RATE_SHARE = 0.8
SLO_MULTIPLES = (5, 6, 8, 10, 13, 19)


def window(server, traffic: dict, seconds: float, seed: int,
           slo_ms: float, counter) -> dict:
    """Serve one window of ``traffic``; its latency statistics."""
    import numpy as np
    from bench.runners import common, serve_open_loop as S
    reqs = S.schedule(traffic, seconds, server.num_nodes, seed)
    ex = server.compiled.engine.block_executor
    compiles0, traces0 = counter.count, ex.trace_count
    t1 = time.monotonic()
    sent = server.serve(reqs, slo_ms)
    server.wait(sent, wait_s=120.0)
    wall = time.monotonic() - t1
    lat = S.latencies_ms(sent)
    third = max(1, len(lat) // 3)
    done = np.isfinite(lat)
    return {"rate_rps": float(traffic["rate_rps"]), "slo_ms": slo_ms,
            "requests": len(sent),
            "p50_ms": common.percentile_nearest(lat, 50),
            "p95_ms": common.percentile_nearest(lat, 95),
            "p99_ms": common.percentile_nearest(lat, 99),
            "missing": int(np.sum(~done)),
            "completed_rps": float(np.sum(done) / wall),
            "lateness_p95_ms": common.percentile_nearest(
                S.lateness_ms(sent), 95),
            "growth": float(np.median(lat[-third:])
                            / np.median(lat[:third])),
            "compiles": counter.count - compiles0 + ex.trace_count
            - traces0}


def steady(row: dict) -> bool:
    return row["missing"] == 0 and row["growth"] < GROWTH_LIMIT


def knee(rows) -> float:
    """The highest rate below the first one that is not steady."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if not steady(r):
            break
        best = r["rate_rps"]
    if best is None:
        raise RuntimeError("no swept rate was steady")
    return best


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=4_000_000_007)
    ap.add_argument("--out", default=None)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload, seed=args.seed,
                             seconds=args.seconds, trace=False)
    harness.require_devices(cell.chips)
    import jax
    from bench.runners import common, serve_open_loop as S
    harness.enable_compile_cache(cell.cache_dir)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"])
    counter = harness.CompileCounter()
    t0 = time.perf_counter()
    arrays = common.load_arrays(cell)
    server = S.Server(cell, arrays)
    print(f"[setup] {time.perf_counter() - t0:.3f} s", flush=True)

    rows, unsteady = [], 0
    for i, rate in enumerate(sorted(float(r)
                                    for r in args.rates.split(","))):
        row = window(server, dict(cell.traffic, rate_rps=rate),
                     args.seconds, common.seed_int(args.seed, 100 + i),
                     NO_DEADLINE_MS, counter)
        rows.append(row)
        print("[rate] " + json.dumps(row), flush=True)
        unsteady = 0 if steady(row) else unsteady + 1
        if unsteady == 2:
            break
    k = knee(rows)
    low = min(rows, key=lambda r: r["rate_rps"])
    rate = int(round(RATE_SHARE * k))
    print(f"[knee] {k} requests/s; cell rate {rate}; low-load p50 "
          f"{low['p50_ms']:.3f} ms at {low['rate_rps']} requests/s",
          flush=True)

    probes, slo = [], None
    for j, mult in enumerate(SLO_MULTIPLES):
        slo_ms = float(round(mult * low["p50_ms"]))
        traffic = dict(cell.traffic, rate_rps=rate, slo_ms=slo_ms)
        server.restart()
        warm = S.schedule(traffic, float(traffic["warm_seconds"]),
                          server.num_nodes,
                          common.seed_int(args.seed, 200 + j))
        server.wait(server.serve(warm, slo_ms))
        row = dict(window(server, traffic, args.seconds,
                          common.seed_int(args.seed, 300 + j), slo_ms,
                          counter), slo_multiple=mult)
        probes.append(row)
        print("[slo] " + json.dumps(row), flush=True)
        if row["missing"] == 0 and row["compiles"] == 0:
            slo = slo_ms
            break
    server.close()
    counter.close()
    print(f"[pick] rate {rate} requests/s, slo "
          f"{'none fits' if slo is None else f'{slo} ms'}", flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(
            {"rates": rows, "knee_rps": k, "rate_rps": rate,
             "low_load_p50_ms": low["p50_ms"], "slo_probes": probes,
             "slo_ms": slo}, indent=1))
    if args.write and slo is not None:
        mix = {w["name"]: w["traffic"] for w in
               harness.load_benchmark(cell.root)["workloads"]}[cell.name]
        path = cell.root / "bench" / "traffic" / f"{mix}.json"
        traffic = json.loads(path.read_text())
        traffic.update(rate_rps=rate, slo_ms=int(slo))
        path.write_text(json.dumps(traffic, indent=2) + "\n")
        print(f"[write] {path}", flush=True)
    return 0 if slo is not None else 1


if __name__ == "__main__":
    sys.exit(main())
