#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

    python bench/calibrate.py --workload <cell> --seeds 12 --controls 3 \
        [--first-seed N] [--seconds S] [--out FILE]

In one process, on the chip the cell runs on:

* the program against the reference on ``--seeds`` seeds (the lower
  readings);
* the control on ``--controls`` of those seeds: the reference computed at
  the precision below the configuration's ("high": three bfloat16 passes)
  in the program's place, against the reference;
* for a training cell, the fault "half of the batch left out, the mean
  taken over the rest", planted in the reference put in the program's
  place, on the same seeds.

Every reading, the program's, the control's and the fault's, is put
through the harness's ``Check`` against the cell's committed limits, as a
run of the cell would judge it, and printed with its verdict: the program
has to come out correct, each control and fault not correct. Writes every
reading and verdict as JSON (``--out``) and prints a summary. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def verdict(cell, readings: dict, names) -> dict:
    """``readings`` judged by the cell's limits, as a run judges them."""
    from bench.harness import Check
    checks = [Check(k, readings[k], cell.limit(k)) for k in names]
    return {"correct": all(c.passed for c in checks),
            "failed": [c.name for c in checks if not c.passed]}


def calibrate_train(cell, seeds, controls):
    from bench.runners import common, train_full as T
    arrays = common.load_arrays(cell)
    steps = int(cell.traffic["first_steps"])
    compiled = common.compile_program(cell, arrays)
    firsts = {}
    for s in seeds:
        cell.seed = s
        prog = T.Program(cell, arrays, compiled=compiled)
        firsts[s] = prog.first_steps(steps)
        prog.close()
        print(f"[program] seed {s}: losses {firsts[s]['losses']}",
              flush=True)
    del compiled
    common.free_device()
    n = int(arrays["node_type"].size)
    out = {"program": {}, "control": {}, "half_batch": {}}
    for i, s in enumerate(seeds):
        cell.seed = s
        ref = T.reference(cell, arrays, steps)
        out["program"][s] = T.compare(firsts[s], ref)
        if i < controls:
            out["control"][s] = T.compare(
                T.reference(cell, arrays, steps, precision="high"), ref)
            half = common.loss_rows(cell, n)[: n // 2]
            out["half_batch"][s] = T.compare(
                T.reference(cell, arrays, steps, rows=half), ref)
        print(f"[reference] seed {s}: {out['program'][s]}", flush=True)
    return out, T.CHECKED


def calibrate_serve(cell, seeds, controls, devices, counter):
    from bench.runners import common, serve_open_loop as S
    out = {"program": {}, "control": {}}
    index = None
    for i, s in enumerate(seeds):
        cell.seed = s
        cell.t_start = time.perf_counter()
        m = S.measure(cell, devices, counter)
        t_ref = time.perf_counter()
        if index is None:
            index = S.GraphIndex(m["arrays"],
                                 cell.config["graph"]["num_etypes"])
        res = S.check_batches(cell, m["arrays"], m["checked"],
                              m["recorder"], index=index)
        out["program"][s] = {"sampler_faults": res["sampler_faults"],
                             "logits_gap": res["logits_gap"],
                             "rows": res["rows"], "p95_ms": m["p95"],
                             "failed": m["failed"],
                             "window_compiles": m["window_compiles"],
                             "setup_s": m["setup_s"],
                             "reference_s": time.perf_counter() - t_ref}
        if i < controls:
            ctl = S.check_batches(cell, m["arrays"], m["checked"],
                                  m["recorder"], precision="high",
                                  index=index)
            # the control replaces the forward only: the blocks are the
            # program's, so its sampler reading is the program's
            out["control"][s] = {
                "sampler_faults": res["sampler_faults"],
                "logits_gap": S.logits_gap(ctl["ref"], res["ref"]),
                "rows": ctl["rows"]}
        print(f"[seed {s}] {out['program'][s]} control "
              f"{out['control'].get(s)}", flush=True)
        common.free_device()
    return out, S.CHECKED


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload, seed=args.first_seed,
                             seconds=args.seconds, trace=False)
    devices = harness.require_devices(cell.chips)
    import jax
    harness.enable_compile_cache(cell.cache_dir)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if cell.traffic["runner"] == "train_full":
        out, names = calibrate_train(cell, seeds, args.controls)
    else:
        out, names = calibrate_serve(cell, seeds, args.controls, devices,
                                     harness.CompileCounter())
    summary, verdicts = {}, {}
    for kind, by_seed in out.items():
        keys = sorted({k for r in by_seed.values() for k in r})
        summary[kind] = {k: [min(r[k] for r in by_seed.values()),
                             max(r[k] for r in by_seed.values())]
                         for k in keys} if by_seed else {}
        verdicts[kind] = {s: verdict(cell, r, names)
                          for s, r in by_seed.items()}
        for s, v in verdicts[kind].items():
            print(f"[verdict] {kind} seed {s}: correct {v['correct']}; "
                  f"over the limit: {', '.join(v['failed']) or 'none'}",
                  flush=True)
    # the program correct on every seed; every control and fault not
    sound = (all(v["correct"] for v in verdicts["program"].values())
             and not any(v["correct"] for kind, by_seed in verdicts.items()
                         if kind != "program" for v in by_seed.values()))
    print(json.dumps({"workload": cell.name, "summary": summary,
                      "as_expected": sound}))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(
            {"workload": cell.name, "limits": cell.limits, "readings": out,
             "verdicts": verdicts, "summary": summary,
             "as_expected": sound}, indent=1, default=float))
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
