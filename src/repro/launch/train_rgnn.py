"""Neighbor-sampled RGNN training driver (RGCN / RGAT / HGT).

The training counterpart of ``serve_rgnn``: seed batches stream through the
epoch-aware shuffled ``EpochSeedStream`` (without replacement) into the
prefetching loader, and every mini-batch runs ONE compiled step — block
forward, per-seed cross-entropy, backward through the gather-fused
``custom_vjp`` kernels, AdamW update — via ``BlockTrainExecutor`` behind
the signature compile cache (zero retraces after the warmup epoch).
Periodic full-graph + sampled evaluation, async checkpointing with
mid-epoch resume, and an optional full-graph parity run (``--parity``)
mirroring the paper's sampled-vs-dense training comparison.

    PYTHONPATH=src python -m repro.launch.train_rgnn --reduced
    PYTHONPATH=src python -m repro.launch.train_rgnn --model hgt \
        --fanout 5,10 --batch-size 64 --epochs 5
    PYTHONPATH=src python -m repro.launch.train_rgnn --reduced --parity
"""
from __future__ import annotations

import argparse
import contextlib

import numpy as np
import jax
import jax.numpy as jnp

import hector
from repro import obs
from repro.core.graph import (CPU_REDUCED_SCALES, synthetic_heterograph,
                              table3_graph)
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamW, cosine_schedule
from repro.sampling import EpochSeedStream, SeedStream
from repro.train import (EngineConfig, MODEL_PROGRAMS, SampledTrainer,
                         parse_fanout)

# synthetic default workload (the example trainer's graph); --reduced scale
SYNTHETIC = dict(num_nodes=2000, num_edges=16000, num_ntypes=4,
                 num_etypes=16, target_compaction=0.5)
SYNTHETIC_REDUCED_SCALE = 0.2


def build_task(dataset: str, scale: float, cfg: EngineConfig, seed: int,
               val_frac: float = 0.2):
    """Graph + engine + a *learnable* node-classification task: labels come
    from a frozen randomly-initialized teacher forward of the same
    architecture, so both trainers can actually fit the data (random labels
    would only measure memorization)."""
    if dataset == "synthetic":
        graph = synthetic_heterograph(
            num_nodes=max(64, int(SYNTHETIC["num_nodes"] * scale)),
            num_edges=max(256, int(SYNTHETIC["num_edges"] * scale)),
            num_ntypes=SYNTHETIC["num_ntypes"],
            num_etypes=SYNTHETIC["num_etypes"], seed=seed,
            target_compaction=SYNTHETIC["target_compaction"])
    else:
        graph = table3_graph(dataset, scale=scale, seed=seed)
    rng = np.random.default_rng(seed)
    # host-side table: the chosen feature store decides what (if anything)
    # becomes device-resident
    feats = rng.normal(size=(graph.num_nodes, cfg.dim)).astype(np.float32)
    # the unified front door (frontend/compile.py) builds program -> plans
    # -> compiled stack -> sampler (+ tuner) from the prebuilt config
    engine = hector.compile(None, graph, config=cfg)
    teacher = engine.init(jax.random.key(seed + 1))
    labels = np.asarray(jnp.argmax(
        engine.forward_full(teacher, jnp.asarray(feats)), -1))
    perm = rng.permutation(graph.num_nodes)
    n_val = int(graph.num_nodes * val_frac)
    val_ids = np.sort(perm[:n_val]).astype(np.int32)
    train_ids = np.sort(perm[n_val:]).astype(np.int32)
    return engine, feats, labels, train_ids, val_ids


def train(
    model: str = "rgat",
    dataset: str = "synthetic",
    scale: float = 1.0,
    layers: int = 2,
    dim: int = 64,
    hidden: int = 64,
    classes: int = 8,
    fanouts=None,
    batch_size: int = 64,
    epochs: int = 3,
    lr: float = 1e-2,
    weight_decay: float = 0.0,
    warmup_steps: int = 5,
    backend: str = "xla",
    tile: int = 32,
    node_block: int = 32,
    bucket: bool = True,
    seed: int = 0,
    sampler: str = "host",
    dp: int = 1,
    partitions=None,
    feature_store: str = "device",
    feature_budget=None,
    skew=None,
    val_frac: float = 0.2,
    ckpt_dir=None,
    ckpt_every: int = 0,
    resume: bool = False,
    eval_every_epochs: int = 0,
    parity: bool = False,
    parity_tol: float = 0.05,
    tune: str = "off",
    tune_cache=None,
    obs_mode: str = "on",
    trace_out=None,
    metrics_out=None,
    profile: bool = False,
    log=print,
):
    """Run the sampled training loop; returns a stats dict (used by the
    tests).

    Observability mirrors ``serve_rgnn``: with ``obs_mode="on"`` the run is
    wrapped in an ``obs.scope`` (per-step latency histograms, cache/trace
    counters, ``stats["metrics"]`` snapshot, optional ``metrics_out``
    export); ``trace_out`` additionally enables phase tracing
    (``sample``/``layout``/``train_step`` spans) and writes a Chrome-trace
    JSON. ``profile=True`` attributes one fused compiled SGD step into
    forward / backward / optimizer via ``obs.profile.profile_train_step``
    (host spans cannot split a single jitted callable).
    """
    with contextlib.ExitStack() as stack:
        sc = None
        if obs_mode == "off":
            stack.enter_context(obs.disabled())
        else:
            sc = stack.enter_context(obs.scope(
                metrics=True, tracing=trace_out is not None))
        return _train_scoped(
            sc, model, dataset, scale, layers, dim, hidden, classes,
            fanouts, batch_size, epochs, lr, weight_decay, warmup_steps,
            backend, tile, node_block, bucket, seed, sampler, dp,
            partitions, feature_store, feature_budget, skew, val_frac,
            ckpt_dir, ckpt_every, resume,
            eval_every_epochs, parity, parity_tol, tune, tune_cache,
            trace_out, metrics_out, profile, log)


def _train_scoped(
    sc, model, dataset, scale, layers, dim, hidden, classes, fanouts,
    batch_size, epochs, lr, weight_decay, warmup_steps, backend, tile,
    node_block, bucket, seed, sampler, dp, partitions, feature_store,
    feature_budget, skew, val_frac, ckpt_dir,
    ckpt_every, resume, eval_every_epochs, parity, parity_tol, tune,
    tune_cache, trace_out, metrics_out, profile, log,
):
    cfg = EngineConfig(model=model, layers=layers, dim=dim, hidden=hidden,
                       classes=classes, fanouts=fanouts, backend=backend,
                       tile=tile, node_block=node_block, bucket=bucket,
                       seed=seed, sampler=sampler, dp=dp,
                       partitions=partitions, feature_store=feature_store,
                       feature_budget=feature_budget, tune=tune,
                       tune_cache=tune_cache)
    engine, feats, labels, train_ids, val_ids = build_task(
        dataset, scale, cfg, seed, val_frac)
    log(f"[train_rgnn] {model} on {dataset} (scale {scale}): "
        f"{engine.graph.num_nodes} nodes, {engine.graph.num_edges} edges, "
        f"{engine.graph.num_etypes} etypes; fanouts={cfg.fanouts}, "
        f"sampler={sampler}, feature_store={feature_store}"
        + (f" skew={skew}" if skew else "")
        + f", {len(train_ids)} train / {len(val_ids)} val nodes")

    # size the LR schedule off the same stream the trainer will iterate
    # (trainer.train rebuilds it from (ids, batch_size, skew), all passed
    # verbatim below; the stream seed never affects sizing)
    if skew is not None:
        bpe = max(1, len(train_ids) // batch_size)
    else:
        bpe = EpochSeedStream(train_ids, batch_size).batches_per_epoch
    total_steps = epochs * bpe
    opt = AdamW(learning_rate=cosine_schedule(lr, warmup_steps, total_steps),
                weight_decay=weight_decay)

    # the feature store; for the cached tier the per-ntype slot split is a
    # measured decision probed on the same traffic the trainer will iterate
    probe = (SeedStream(ids=train_ids, batch_size=batch_size, seed=seed,
                        zipf_alpha=skew) if skew is not None
             else EpochSeedStream(train_ids, batch_size, seed=seed))
    store = engine.make_feature_store(feats, seed_source=probe)
    if feature_store == "cached":
        log(f"[train_rgnn] feature cache: {store.capacity} device rows "
            f"({store.device_bytes() / 1e6:.2f} MB vs full table "
            f"{store.table_bytes / 1e6:.2f} MB), per-ntype slots "
            f"{store.slot_ptr.tolist()}")

    if cfg.distributed:
        return _train_dist(engine, store, labels, train_ids, val_ids, opt,
                           epochs, batch_size, bpe, seed, parity, profile,
                           ckpt_dir, resume, sc, metrics_out, log)

    trainer = SampledTrainer(engine, store, labels, train_ids, val_ids,
                             opt=opt, ckpt_dir=ckpt_dir, log=log)
    state = trainer.init_state(engine.init(jax.random.key(seed)))

    if tune != "off":
        # block-scale tuning on one representative training batch (bucketed
        # shapes make the decisions valid for the whole epoch stream)
        warm_seeds = np.sort(np.random.default_rng(seed + 1).choice(
            train_ids, size=min(batch_size, len(train_ids)),
            replace=False)).astype(np.int32)
        tl = engine.make_loader(lambda step: warm_seeds, num_batches=1,
                                depth=1)
        try:
            engine.tune_minibatch(state.params, next(tl), jnp.asarray(feats))
        finally:
            tl.close()
        ts = engine.tuner_stats
        log(f"[train_rgnn] tune={tune}: {ts.get('measurements', 0)} "
            f"measurements, {ts.get('cache_hits', 0)} cache replays "
            f"(tile {engine.tile}, node_block {engine.node_block})")

    start_step = 0
    if resume:
        state, start_step = trainer.resume(state)
        if start_step:
            log(f"[train_rgnn] resumed from step {start_step} "
                f"(epoch {start_step // bpe}, batch {start_step % bpe})")

    state, stats = trainer.train(
        state, epochs=epochs, batch_size=batch_size, start_step=start_step,
        ckpt_every=ckpt_every, eval_every_epochs=eval_every_epochs,
        log_every=max(1, bpe // 2), skew=skew)

    for k, v in engine.tuner_stats.items():
        stats[f"tune_{k}"] = v
    dev_sampler = getattr(engine, "device_sampler", None)
    if dev_sampler is not None:
        for k, v in dev_sampler.stats().items():
            stats[f"sampler_{k}"] = v
        log(f"[train_rgnn] device sampler: "
            f"{dev_sampler.trace_count} traces / "
            f"{dev_sampler.cache_hits} program-cache hits over "
            f"{dev_sampler.batches_sampled} batches")
    final_train = trainer.full.evaluate(state.params)
    final_val = (trainer.full.evaluate(state.params, val_ids)
                 if len(val_ids) else None)
    stats["full_train_loss"] = final_train["loss"]
    stats["full_train_acc"] = final_train["accuracy"]
    if final_val is not None:
        stats["full_val_loss"] = final_val["loss"]
        stats["full_val_acc"] = final_val["accuracy"]
    log(f"[train_rgnn] sampled training done: {stats['steps']} steps, "
        f"step p50 {stats['step_ms_p50']:.1f} ms, "
        f"{stats['seeds_per_s']:.1f} seeds/s, "
        f"{stats['retraces_after_warmup']} retraces after warmup "
        f"({stats['executor_compiled']} compiled buckets)")
    log(f"[train_rgnn] full-graph eval: train loss {final_train['loss']:.4f} "
        f"acc {final_train['accuracy']:.2%}"
        + (f" | val loss {final_val['loss']:.4f} "
           f"acc {final_val['accuracy']:.2%}" if final_val else ""))

    if parity:
        # dense baseline: same init, same optimizer-step budget; parity is
        # judged on *held-out* loss (mini-batch SGD trades per-step training
        # loss for more updates, so train-loss comparison at equal step
        # count is dominated by that trade — generalization is the
        # apples-to-apples metric). With no val split, falls back to train.
        fg = trainer.full   # identical config: reuse its compiled step
        fstate = fg.init_state(engine.init_params(jax.random.key(seed)))
        fstate, _ = fg.train(fstate, steps=total_steps,
                             log_every=max(1, total_steps // 4))
        if len(val_ids):
            split, sampled_loss = "val", final_val["loss"]
            fg_loss = fg.evaluate(fstate.params, val_ids)["loss"]
        else:
            split, sampled_loss = "train", final_train["loss"]
            fg_loss = fg.evaluate(fstate.params)["loss"]
        gap = (sampled_loss - fg_loss) / max(fg_loss, 1e-6)
        stats["parity_full_graph_loss"] = fg_loss
        stats["parity_gap"] = gap
        ok = gap <= parity_tol
        log(f"[train_rgnn] parity ({split} loss): sampled "
            f"{sampled_loss:.4f} vs full-graph {fg_loss:.4f} "
            f"(gap {gap:+.1%}, tol {parity_tol:.0%}) -> "
            f"{'OK' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(
                f"sampled {split} loss {sampled_loss:.4f} not within "
                f"{parity_tol:.0%} of full-graph {fg_loss:.4f}")

    if profile:
        # forward/backward/optimizer attribution of ONE fused compiled
        # step, on a representative (bucketed) batch off the epoch stream
        from repro.obs import profile as prof_mod
        warm_seeds = np.sort(np.random.default_rng(seed + 2).choice(
            train_ids, size=min(batch_size, len(train_ids)),
            replace=False)).astype(np.int32)
        pl = engine.make_loader(lambda step: warm_seeds, num_batches=1,
                                depth=1)
        try:
            mb = next(pl)
        finally:
            pl.close()
        ph = prof_mod.profile_train_step(
            engine.plans, trainer.opt, state, mb,
            mb.seq.slice_labels(labels),
            {"feature": jnp.asarray(feats)[mb.input_ids]},
            backend=engine.cfg.backend, activation=engine.cfg.activation,
            decisions=engine.decisions, warmup=1, iters=5)
        log(f"[train_rgnn] step attribution: "
            f"forward {ph['forward']*1e3:.2f} ms, "
            f"backward {ph['backward']*1e3:.2f} ms, "
            f"optimizer {ph['optimizer']*1e3:.2f} ms "
            f"(fused step {ph['total']*1e3:.2f} ms)")
        stats["profile"] = {k: v * 1e3 for k, v in ph.items()}

    if sc is not None:
        if sc.tracer is not None:
            log("[train_rgnn] phase table:\n" + sc.tracer.phase_table())
            if trace_out:
                sc.tracer.write(trace_out)
                log(f"[train_rgnn] chrome trace -> {trace_out}")
        stats["metrics"] = sc.registry.snapshot()
        if metrics_out:
            sc.registry.export(metrics_out)
            log(f"[train_rgnn] metrics snapshot -> {metrics_out}")
    return stats


def _train_dist(engine, feats, labels, train_ids, val_ids, opt, epochs,
                batch_size, bpe, seed, parity, profile, ckpt_dir, resume,
                sc, metrics_out, log):
    """Data-parallel training loop (``--dp`` / ``--partitions``): sharded
    sampling + one compiled shard_map step per batch, no per-step host
    sync; final evaluation runs the usual full-graph compiled step."""
    if parity or profile or ckpt_dir or resume:
        raise ValueError("--parity/--profile/--ckpt-dir/--resume are not "
                         "supported together with --dp/--partitions")
    from repro.dist import DistTrainer
    from repro.train import FullGraphTrainer
    cfg = engine.cfg
    log(f"[train_rgnn] distributed: {cfg.num_partitions} shards over "
        f"{cfg.dp} devices\n" + engine.partition.describe())
    trainer = DistTrainer(engine, feats, labels, train_ids, val_ids,
                          opt=opt, log=log)
    state = trainer.init_state(engine.init(jax.random.key(seed)))
    state, stats = trainer.train(state, epochs=epochs,
                                 batch_size=batch_size,
                                 log_every=max(1, bpe // 2))

    full = FullGraphTrainer(engine, feats, labels, train_ids, opt=opt,
                            log=log)
    final_train = full.evaluate(state.params)
    final_val = (full.evaluate(state.params, val_ids)
                 if len(val_ids) else None)
    stats["full_train_loss"] = final_train["loss"]
    stats["full_train_acc"] = final_train["accuracy"]
    if final_val is not None:
        stats["full_val_loss"] = final_val["loss"]
        stats["full_val_acc"] = final_val["accuracy"]
    log(f"[train_rgnn] dist training done: {stats['steps']} steps on "
        f"{cfg.num_partitions} shards / {cfg.dp} devices, "
        f"step p50 {stats['step_ms_p50']:.1f} ms, "
        f"{stats['seeds_per_s']:.1f} seeds/s, "
        f"{stats['retraces_after_warmup']} retraces after warmup "
        f"({stats['executor_compiled']} compiled buckets)")
    log(f"[train_rgnn] full-graph eval: train loss {final_train['loss']:.4f} "
        f"acc {final_train['accuracy']:.2%}"
        + (f" | val loss {final_val['loss']:.4f} "
           f"acc {final_val['accuracy']:.2%}" if final_val else ""))
    from repro.feats import is_feature_store
    if is_feature_store(feats):
        for k, v in feats.stats().items():
            stats[f"feature_{k}"] = v
    if sc is not None:
        stats["metrics"] = sc.registry.snapshot()
        if metrics_out:
            sc.registry.export(metrics_out)
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="rgat", choices=sorted(MODEL_PROGRAMS))
    ap.add_argument("--dataset", default="synthetic",
                    choices=["synthetic"] + sorted(CPU_REDUCED_SCALES))
    ap.add_argument("--reduced", action="store_true",
                    help="scale the dataset for CPU tractability")
    ap.add_argument("--scale", type=float, default=None,
                    help="explicit dataset scale factor (overrides --reduced)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--fanout", default="5",
                    help="per-hop fanout, e.g. '5' or '5,10'; -1 = full")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "pallas_interpret"])
    ap.add_argument("--tile", type=int, default=32)
    ap.add_argument("--node-block", type=int, default=32)
    ap.add_argument("--no-bucket", action="store_true")
    ap.add_argument("--sampler", default="host", choices=["host", "device"],
                    help="'host': NumPy fanout sampling + host layout "
                         "build; 'device': jit-compiled sampling + layout "
                         "over a device-resident CSC")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel device count: shard the graph and "
                         "run each SGD step across all shards under one "
                         "compiled shard_map step (all-reduce inside)")
    ap.add_argument("--partitions", type=int, default=None,
                    help="graph shard count (default: one per --dp device; "
                         "a multiple of --dp folds extra shards onto "
                         "devices with bit-identical results)")
    ap.add_argument("--feature-store", default="device",
                    choices=["device", "host", "cached"],
                    help="where the node-feature table lives: 'device' = "
                         "full table device-resident (baseline); 'host' = "
                         "host tables, per-batch input rows gathered inside "
                         "the prefetch overlap; 'cached' = host tables "
                         "fronted by a fixed-budget device hot-row cache")
    ap.add_argument("--feature-budget", type=int, default=None,
                    help="device hot-row count for --feature-store cached "
                         "(default: num_nodes // 4), split per ntype by "
                         "measured traffic")
    ap.add_argument("--skew", type=float, default=None, metavar="ALPHA",
                    help="Zipf-skew the seed stream (rank probability "
                         "(r+1)^-ALPHA, with replacement) — the power-law "
                         "traffic model for feature-cache studies")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--val-frac", type=float, default=0.2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps (0 disables)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--eval-every-epochs", type=int, default=1)
    ap.add_argument("--parity", action="store_true",
                    help="also run the full-graph trainer with the same "
                         "step budget and assert the sampled loss is within "
                         "--parity-tol of it")
    ap.add_argument("--parity-tol", type=float, default=0.05)
    ap.add_argument("--tune", default="off",
                    choices=["off", "cached", "full"],
                    help="autotune operator variants: 'cached' replays the "
                         "persistent cache with zero measurements, 'full' "
                         "measures missing entries on-device")
    ap.add_argument("--tune-cache", default=None,
                    help="persistent tuning-cache path (default "
                         "$REPRO_TUNE_CACHE or ~/.cache/repro-tune.json)")
    ap.add_argument("--obs", default="on", choices=["on", "off"],
                    help="observability: 'on' runs inside an obs scope "
                         "(metrics registry + stats['metrics']); 'off' is "
                         "the zero-instrumentation baseline")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable phase tracing and write a Chrome-trace "
                         "JSON (load in chrome://tracing or Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot JSON here")
    ap.add_argument("--profile", action="store_true",
                    help="attribute one fused compiled SGD step into "
                         "forward / backward / optimizer phases")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.scale is not None:
        scale = args.scale
    elif args.reduced:
        scale = (SYNTHETIC_REDUCED_SCALE if args.dataset == "synthetic"
                 else CPU_REDUCED_SCALES[args.dataset])
    else:
        scale = 1.0
    return train(
        model=args.model, dataset=args.dataset, scale=scale,
        layers=args.layers, dim=args.dim, hidden=args.hidden,
        classes=args.classes,
        fanouts=parse_fanout(args.fanout, args.layers),
        batch_size=args.batch_size, epochs=args.epochs, lr=args.lr,
        weight_decay=args.weight_decay, backend=args.backend,
        tile=args.tile, node_block=args.node_block,
        bucket=not args.no_bucket, seed=args.seed, sampler=args.sampler,
        dp=args.dp, partitions=args.partitions,
        feature_store=args.feature_store,
        feature_budget=args.feature_budget, skew=args.skew,
        val_frac=args.val_frac,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, eval_every_epochs=args.eval_every_epochs,
        parity=args.parity, parity_tol=args.parity_tol,
        tune=args.tune, tune_cache=args.tune_cache,
        obs_mode=args.obs, trace_out=args.trace_out,
        metrics_out=args.metrics_out, profile=args.profile,
    )


if __name__ == "__main__":
    main()
