"""Distributed execution gate: sharded serving + data-parallel training.

Pins the contract of the data-parallel layer on forced-CPU hardware:

* **zero retraces after warmup across shards** — multi-shard serving over
  repeat traffic and the data-parallel training loop both replay their
  compiled ``shard_map`` step after the warmup window (the per-shard
  bucketing would otherwise retrace on every routing change);
* **all-reduce fused into one compiled step** — the lowered StableHLO of
  the train step contains the halo-feature all-gather and the gradient
  all-reduce collectives inside the single jitted module (no separate
  communication dispatches), and repeat steps stay on one cache entry;
* **dp=4 parity** — a subprocess with 4 forced host devices checks that
  serve logits, train loss, and the full updated optimizer state are
  bitwise identical between dp=1 (4 shards folded on one device) and dp=4
  (1 shard per device).

Everything here runs on the CPU. The parity child is started after this
process has already initialized JAX, so it forces ``JAX_PLATFORMS=cpu``:
on a machine with TPUs it still sees only four host devices, never the
chips (the parent holds them). This checks the partitioned program's
semantics, not the chips; the four-chip check is
``python chip_smoke.py --four-chips``, in one process.

``--ci`` turns any violation into a failing exit code.

    PYTHONPATH=src python -m benchmarks.dist_smoke --ci
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
from typing import List

from benchmarks.common import csv_row

# multi-shard serving over repeat traffic (dp=1: 4 logical shards folded
# onto the one real device; the shard_map program is identical at dp=4)
SERVE_CONFIG = dict(
    model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], batch_size=8, num_batches=9, tile=8,
    node_block=8, repeat_after=3, seed=0, partitions=4, obs_mode="off",
)

# data-parallel training loop: 64 seeds x batch 16 over 2 epochs; epoch 1
# is warmup (traces every shuffled bucket combination), epoch 2 must replay
TRAIN_CONFIG = dict(num_ids=64, batch_size=16, epochs=2, warmup_epochs=1)

# dp=1 vs dp=4 bitwise parity + fused-collective HLO check, run in a
# subprocess so the host platform can be split into 4 devices
_DP4_CODE = """
    import json
    import numpy as np, jax, jax.numpy as jnp
    assert jax.device_count() == 4, jax.devices()
    from repro.core.graph import synthetic_heterograph
    from repro.dist import (partition_graph, ShardedBatcher,
                            ShardedServeExecutor, ShardedTrainExecutor)
    from repro.launch.mesh import make_data_mesh
    from repro.optim import AdamW
    from repro.train import EngineConfig, RGNNEngine

    g = synthetic_heterograph(120, 900, 4, 7, seed=0)
    part = partition_graph(g, 4)
    SEEDS = np.array([3, 50, 7, 3, 119, 0, 88, 12], dtype=np.int32)
    eng = RGNNEngine(g, EngineConfig(
        model="rgat", layers=2, dim=16, hidden=12, classes=6,
        fanouts=[3, 3], tile=8, node_block=8, seed=0))
    rng = np.random.default_rng(1)
    feats = np.asarray(rng.normal(size=(g.num_nodes, 16)), np.float32)
    labels = np.asarray(rng.integers(0, 6, g.num_nodes))
    params = eng.init_params(jax.random.key(0))
    own = jnp.asarray(part.shard_features(feats))
    smb = ShardedBatcher(part, [3, 3], seed=0, tile=8,
                         node_block=8).build(SEEDS, step=0, epoch=0)
    opt = AdamW(learning_rate=1e-2, weight_decay=0.01)

    out = {}
    for dp in (1, 4):
        mesh = make_data_mesh(dp)
        logits = np.asarray(ShardedServeExecutor(eng.plans, mesh)
                            .run_minibatch(params, smb, own))
        st, m = ShardedTrainExecutor(eng.plans, opt, mesh) \\
            .grad_and_update(opt.init(params), smb, labels, own)
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            (st.params, st.mu, st.nu))]
        out[dp] = (logits, float(m["loss"]), leaves)
    parity = (bool((out[1][0] == out[4][0]).all())
              and out[1][1] == out[4][1]
              and all((a == b).all() for a, b in zip(out[1][2], out[4][2])))

    # the fused step at dp=4: the collectives must live inside the one
    # lowered module, and repeat steps must stay on one cache entry
    mesh = make_data_mesh(4)
    tr = ShardedTrainExecutor(eng.plans, opt, mesh)
    hlo = tr.lowered_hlo(opt.init(params), smb, labels, own)
    state = opt.init(params)
    for _ in range(3):
        state, _m = tr.grad_and_update(state, smb, labels, own)
    print(json.dumps({
        "parity": parity,
        "hlo_all_gathers": hlo.count("all_gather"),
        "train_compiled": tr.num_compiled,
        "train_cache_hits": tr.cache_hits,
    }))
"""


def _quiet(*_a, **_k):
    pass


def _run_dp4_subprocess() -> dict:
    """Run the parity/HLO check under 4 forced host CPU devices (never the
    chips: see the module docstring); returns the JSON result dict printed
    by the child."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_DP4_CODE)],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"dp4 subprocess failed:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _run_train() -> dict:
    """Data-parallel training loop on a synthetic partitioned graph; the
    epoch-2 steps must all replay epoch-1 traces."""
    import numpy as np
    import jax
    from repro.core.graph import synthetic_heterograph
    from repro.dist import DistTrainer
    from repro.train import EngineConfig, RGNNEngine

    g = synthetic_heterograph(120, 900, 4, 7, seed=0)
    eng = RGNNEngine(g, EngineConfig(
        model="rgat", layers=2, dim=16, hidden=12, classes=6,
        fanouts=[3, 3], tile=8, node_block=8, seed=0, partitions=4))
    rng = np.random.default_rng(1)
    feats = np.asarray(rng.normal(size=(g.num_nodes, 16)), np.float32)
    labels = np.asarray(rng.integers(0, 6, g.num_nodes))
    ids = np.arange(0, TRAIN_CONFIG["num_ids"], dtype=np.int32)
    tr = DistTrainer(eng, feats, labels, ids, log=None)
    state = tr.init_state(eng.init_params(jax.random.key(0)))
    t0 = time.perf_counter()
    _state, stats = tr.train(
        state, epochs=TRAIN_CONFIG["epochs"],
        batch_size=TRAIN_CONFIG["batch_size"],
        warmup_epochs=TRAIN_CONFIG["warmup_epochs"])
    stats["wall_s"] = time.perf_counter() - t0
    return stats


def run(out=print):
    """Serve + train + dp4 parity; returns ``(problems, serve_stats,
    train_stats, dp4_result)``."""
    from repro.launch.serve_rgnn import serve

    s = serve(log=_quiet, **SERVE_CONFIG)
    t = _run_train()
    d = _run_dp4_subprocess()

    problems: List[str] = []
    if s["retraces_after_warmup"] != 0:
        problems.append(
            f"multi-shard serve retraced {s['retraces_after_warmup']} "
            f"times after warmup (want 0)")
    if s["batcher_batch_cache"]["hits"] <= 0:
        problems.append("sharded batcher never reused a cached batch on "
                        "repeat traffic")
    if t["retraces_after_warmup"] != 0:
        problems.append(
            f"data-parallel trainer retraced {t['retraces_after_warmup']} "
            f"times after the warmup epoch (want 0)")
    if not (t["losses"][-1] < t["losses"][0]):
        problems.append(
            f"train loss did not decrease ({t['losses'][0]:.4f} -> "
            f"{t['losses'][-1]:.4f})")
    if not d["parity"]:
        problems.append("dp=4 is not bitwise identical to dp=1 "
                        "(serve logits / loss / optimizer state)")
    if d["hlo_all_gathers"] < 2:
        problems.append(
            f"lowered train step contains {d['hlo_all_gathers']} all_gather "
            f"collectives (want >=2: halo features + gradient all-reduce "
            f"fused into the one compiled module)")
    if d["train_compiled"] != 1 or d["train_cache_hits"] < 2:
        problems.append(
            f"dp=4 train step not served from one compiled entry "
            f"(compiled={d['train_compiled']}, hits={d['train_cache_hits']})")

    out(csv_row("dist_smoke/serve", s["latency_ms_p50"] / 1e3,
                f"shards={s['num_partitions']};dp={s['dp']};"
                f"retraces={s['retraces_after_warmup']};"
                f"compiled={s['executor_compiled']};"
                f"batch_cache_hits={s['batcher_batch_cache']['hits']}"))
    out(csv_row("dist_smoke/train", t["step_ms_p50"] / 1e3,
                f"steps={t['steps']};retraces={t['retraces_after_warmup']};"
                f"compiled={t['executor_compiled']};"
                f"loss={t['losses'][0]:.3f}->{t['losses'][-1]:.3f}"))
    out(csv_row("dist_smoke/dp4", 0.0,
                f"parity={'ok' if d['parity'] else 'FAIL'};"
                f"hlo_all_gathers={d['hlo_all_gathers']};"
                f"compiled={d['train_compiled']};"
                f"problems={len(problems)}"))
    return problems, s, t, d


def ci_check() -> None:
    """Exit 1 unless serving and training replay across shards, the
    collectives are fused into the compiled step, and dp=4 == dp=1."""
    problems, s, t, d = run(out=lambda *_: None)
    if problems:
        for pb in problems:
            print(f"[dist_smoke --ci] FAIL: {pb}", file=sys.stderr)
        raise SystemExit(1)
    print(f"[dist_smoke --ci] OK: {s['num_partitions']}-shard serve "
          f"{s['batches']} batches (0 retraces after warmup), "
          f"dist train {t['steps']} steps (0 retraces, loss "
          f"{t['losses'][0]:.3f}->{t['losses'][-1]:.3f}), dp4 bitwise "
          f"parity, {d['hlo_all_gathers']} all_gathers fused into "
          f"{d['train_compiled']} compiled step")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ci", action="store_true",
                    help="fail (exit 1) on any distributed-contract "
                         "violation")
    args = ap.parse_args(argv)
    if args.ci:
        ci_check()
    else:
        print("name,us_per_call,derived")
        problems, *_ = run()
        for pb in problems:
            print(f"[dist_smoke] problem: {pb}", file=sys.stderr)


if __name__ == "__main__":
    main()
