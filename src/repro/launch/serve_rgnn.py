"""Batched RGNN inference serving driver.

Request batches of seed nodes stream through the fanout sampler (prefetched
on a background thread, kernel layouts built off the accelerator path), and
a multi-layer Hector stack runs one generated layer per sampled hop through
the whole-plan compiled ``BlockExecutor``, returning per-seed logits.
Reports per-batch latency split into queue-wait (sampling + layout, when not
hidden by prefetch) and model compute, end-to-end seed throughput, and —
when the caches are enabled — sampled-block / layout cache hit rates plus
compiled-executor trace counts (``retraces_after_warmup`` pins the
steady-state zero-retrace invariant).

    PYTHONPATH=src python -m repro.launch.serve_rgnn --model rgat --reduced
    PYTHONPATH=src python -m repro.launch.serve_rgnn \
        --model hgt --dataset mutag --fanout 5,10 --batch-size 64
    # power-law repeat traffic over 4 distinct batches, all caches on:
    PYTHONPATH=src python -m repro.launch.serve_rgnn --repeat-after 4 \
        --cache-blocks 64 --cache-layouts 256
"""
from __future__ import annotations

import argparse
import contextlib
import time

import numpy as np
import jax
import jax.numpy as jnp

import hector
from repro import obs
from repro.core.graph import CPU_REDUCED_SCALES as REDUCED_SCALES
from repro.core.graph import table3_graph
from repro.launch.compile_cache import enable_compile_cache
from repro.sampling import SeedStream
from repro.train.engine import MODEL_PROGRAMS, parse_fanout


def serve(
    model: str = "rgat",
    dataset: str = "aifb",
    scale: float = 1.0,
    layers: int = 2,
    dim: int = 64,
    hidden: int = 64,
    classes: int = 16,
    fanouts=None,
    batch_size: int = 32,
    num_batches: int = 8,
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
    prefetch_depth: int = 2,
    cache_blocks: int = 0,
    cache_layouts: int = 0,
    repeat_after=None,
    compiled: bool = True,
    warmup_batches=None,
    tune: str = "off",
    tune_cache=None,
    obs_mode: str = "on",
    trace_out=None,
    metrics_out=None,
    profile: bool = False,
    log=print,
):
    """Run the serving loop; returns a stats dict (used by tests/benchmarks).

    ``repeat_after`` wraps the seed stream onto that many distinct batches
    (power-law repeat traffic). ``warmup_batches`` (default: ``repeat_after``
    or 2) splits the trace accounting: compiles during warmup are expected,
    any after it count as ``retraces_after_warmup``.

    Observability: with ``obs_mode="on"`` the call runs inside an
    ``obs.scope`` — latency histograms and cache/trace counters land in a
    metrics registry whose snapshot is returned as ``stats["metrics"]``
    (and written to ``metrics_out`` if given). ``trace_out`` additionally
    enables phase tracing (``sample``/``layout``/``execute`` spans) and
    writes a Chrome-trace JSON there. ``profile=True`` runs the per-op
    plan profiler on the last served mini-batch and attaches the breakdown
    as ``stats["profile"]``. ``obs_mode="off"`` serves with observability
    fully disabled (the <2%-overhead baseline).
    """
    if warmup_batches is None:
        warmup_batches = repeat_after if repeat_after else 2
    warmup_batches = min(warmup_batches, num_batches)

    with contextlib.ExitStack() as stack:
        sc = None
        if obs_mode == "off":
            stack.enter_context(obs.disabled())
        else:
            sc = stack.enter_context(obs.scope(
                metrics=True, tracing=trace_out is not None))
        return _serve_scoped(
            sc, model, dataset, scale, layers, dim, hidden, classes,
            fanouts, batch_size, num_batches, backend, tile, node_block,
            bucket, seed, sampler, dp, partitions, feature_store,
            feature_budget, skew, prefetch_depth,
            cache_blocks, cache_layouts, repeat_after, compiled,
            warmup_batches, tune, tune_cache, trace_out, metrics_out,
            profile, log)


def _serve_scoped(
    sc, model, dataset, scale, layers, dim, hidden, classes, fanouts,
    batch_size, num_batches, backend, tile, node_block, bucket, seed,
    sampler, dp, partitions, feature_store, feature_budget, skew,
    prefetch_depth, cache_blocks, cache_layouts,
    repeat_after, compiled, warmup_batches, tune, tune_cache, trace_out,
    metrics_out, profile, log,
):

    t0 = time.perf_counter()
    graph = table3_graph(dataset, scale=scale, seed=seed)
    rng = np.random.default_rng(seed)
    # host-side table: the chosen feature store decides what (if anything)
    # becomes device-resident
    feats = rng.normal(size=(graph.num_nodes, dim)).astype(np.float32)
    t_graph = time.perf_counter() - t0

    # the unified front door: program -> plans -> compiled stack -> sampler
    # (+ tuner), one call (frontend/compile.py)
    engine = hector.compile(
        model, graph, layers=layers, dim=dim, hidden=hidden,
        classes=classes, sample=fanouts, backend=backend, tile=tile,
        node_block=node_block, bucket=bucket, seed=seed, sampler=sampler,
        dp=dp, partitions=partitions, feature_store=feature_store,
        feature_budget=feature_budget, tune=tune, tune_cache=tune_cache,
        tune_full_graph=False, log=log)
    fanouts = engine.cfg.fanouts
    log(f"[serve_rgnn] {model} on {dataset} (scale {scale}): "
        f"{graph.num_nodes} nodes, {graph.num_edges} edges, "
        f"{graph.num_etypes} etypes; fanouts={fanouts} "
        f"sampler={sampler} feature_store={feature_store}"
        + (f" skew={skew}" if skew else "")
        + f" (graph build {t_graph:.2f}s)")
    params = engine.init(jax.random.key(seed))

    stream = SeedStream(graph.num_nodes, batch_size, seed=seed,
                        num_distinct=repeat_after, zipf_alpha=skew)
    # the feature store; for the cached tier the per-ntype slot split is a
    # measured decision probed on this exact traffic (tune.feature_budget)
    store = engine.make_feature_store(feats, seed_source=stream)
    if feature_store == "cached":
        log(f"[serve_rgnn] feature cache: {store.capacity} device rows "
            f"({store.device_bytes() / 1e6:.2f} MB vs full table "
            f"{store.table_bytes / 1e6:.2f} MB), per-ntype slots "
            f"{store.slot_ptr.tolist()}")

    if engine.cfg.distributed:
        return _serve_dist(engine, graph, store, params, batch_size,
                           num_batches, repeat_after, warmup_batches, seed,
                           skew, sc, metrics_out, log)

    if tune != "off":
        # block-scale tuning on one representative (bucketed) mini-batch,
        # off the serving stream so traffic is untouched; with a warm
        # persistent cache this replays decisions with zero measurements
        warm_seeds = np.random.default_rng(seed + 1).integers(
            0, graph.num_nodes, batch_size).astype(np.int32)
        tl = engine.make_loader(lambda step: warm_seeds, num_batches=1,
                                depth=1)
        try:
            engine.tune_minibatch(params, next(tl), jnp.asarray(feats))
        finally:
            tl.close()
        ts = engine.tuner_stats
        log(f"[serve_rgnn] tune={tune}: {ts.get('measurements', 0)} "
            f"measurements, {ts.get('cache_hits', 0)} cache replays "
            f"(tile {engine.tile}, node_block {engine.node_block})")

    loader = engine.make_loader(
        stream,
        num_batches=num_batches, depth=prefetch_depth,
        cache_blocks=cache_blocks, cache_layouts=cache_layouts,
        feature_store=store,
    )

    executor = engine.block_executor
    metrics = obs.metrics()
    h_lat = metrics.histogram("serve_batch_ms")
    h_wait = metrics.histogram("serve_wait_ms")
    h_compute = metrics.histogram("serve_compute_ms")
    lat, waits, computes, preds = [], [], [], None
    edges_seen = 0
    retraces_after_warmup = 0
    traces_at_warmup = None
    dev_sampler = getattr(engine, "device_sampler", None)
    sampler_traces_at_warmup = None
    sampler_syncs_at_warmup = None
    last_mb = None
    t_serve0 = time.perf_counter()
    try:
        while True:
            t0 = time.perf_counter()
            with obs.span("wait", batch=len(lat)):
                try:
                    mb = next(loader)
                except StopIteration:
                    break
            t_wait = time.perf_counter() - t0
            if len(lat) == warmup_batches:
                traces_at_warmup = executor.trace_count
                if dev_sampler is not None:
                    sampler_traces_at_warmup = dev_sampler.trace_count
                    sampler_syncs_at_warmup = dev_sampler.count_syncs
            t0 = time.perf_counter()
            # engine.apply_blocks opens the "execute" span (with a device
            # sync inside it when tracing is on); the loader attached this
            # batch's features (mb.feats) through the store
            logits = engine.apply_blocks(params, mb, store,
                                         compiled=compiled)
            logits.block_until_ready()
            t_fwd = time.perf_counter() - t0
            lat.append(t_wait + t_fwd)
            waits.append(t_wait)
            computes.append(t_fwd)
            h_lat.observe((t_wait + t_fwd) * 1e3)
            h_wait.observe(t_wait * 1e3)
            h_compute.observe(t_fwd * 1e3)
            edges_seen += sum(gt.num_edges for gt in mb.tensors)
            preds = np.asarray(jnp.argmax(logits, axis=-1))
            last_mb = mb
            hops = "+".join(str(b.num_src) for b in mb.seq.blocks)
            log(f"[serve_rgnn] batch {mb.step}: wait {t_wait*1e3:6.1f} ms, "
                f"forward {t_fwd*1e3:6.1f} ms  (block nodes {hops})")
    finally:
        loader.close()
    t_total = time.perf_counter() - t_serve0
    if traces_at_warmup is not None:
        retraces_after_warmup = executor.trace_count - traces_at_warmup

    n = len(lat)
    if n == 0:
        raise RuntimeError("no batches served")
    lat_arr = np.asarray(lat)
    stats = {
        "batches": n,
        "batch_size": batch_size,
        "latency_ms_p50": float(np.percentile(lat_arr, 50) * 1e3),
        "latency_ms_p95": float(np.percentile(lat_arr, 95) * 1e3),
        "latency_ms_p99": float(np.percentile(lat_arr, 99) * 1e3),
        "latency_ms_mean": float(lat_arr.mean() * 1e3),
        "wait_ms_mean": float(np.mean(waits) * 1e3),
        "compute_ms_mean": float(np.mean(computes) * 1e3),
        "seeds_per_s": batch_size * n / max(t_total, 1e-9),
        "edges_per_batch": edges_seen / n,
        "last_preds": preds,
        "warmup_batches": warmup_batches,
        "executor_traces": executor.trace_count,
        "executor_cache_hits": executor.cache_hits,
        "executor_compiled": executor.num_compiled,
        "retraces_after_warmup": retraces_after_warmup,
        "sampler": loader.mode,
        "host_builds": loader.host_builds,
        "device_builds": loader.device_builds,
    }
    if dev_sampler is not None:
        stats["sampler_traces"] = dev_sampler.trace_count
        stats["sampler_retraces_after_warmup"] = (
            dev_sampler.trace_count - sampler_traces_at_warmup
            if sampler_traces_at_warmup is not None else 0)
        stats["sampler_count_syncs"] = dev_sampler.count_syncs
        stats["sampler_count_syncs_after_warmup"] = (
            dev_sampler.count_syncs - sampler_syncs_at_warmup
            if sampler_syncs_at_warmup is not None
            else dev_sampler.count_syncs)
        stats["sampler_bucket_overflows"] = dev_sampler.bucket_overflows
        stats["sampler_bucket_shrinks"] = dev_sampler.bucket_shrinks
        log(f"[serve_rgnn] device sampler: {dev_sampler.trace_count} traces "
            f"/ {dev_sampler.cache_hits} program-cache hits "
            f"({stats['sampler_retraces_after_warmup']} retraces after "
            f"warmup); {dev_sampler.count_syncs} count syncs, "
            f"{dev_sampler.bucket_shrinks} bucket shrinks, "
            f"{dev_sampler.bucket_overflows} overflows; builds host "
            f"{loader.host_builds} / device {loader.device_builds}")
    if obs.metrics_enabled():
        # registry-sourced latency percentiles (the reservoir keeps every
        # sample at this scale, so these match the array-side numbers)
        hs = metrics.histogram_summary("serve_batch_ms")
        stats["latency_ms_p50"] = hs["p50"]
        stats["latency_ms_p95"] = hs["p95"]
        stats["latency_ms_p99"] = hs["p99"]
    for k, v in engine.tuner_stats.items():
        stats[f"tune_{k}"] = v
    for name, cs in loader.cache_stats().items():
        stats[f"{name}_hits"] = cs["hits"]
        stats[f"{name}_misses"] = cs["misses"]
        stats[f"{name}_hit_rate"] = cs["hit_rate"]
    for k, v in store.stats().items():
        stats[f"feature_{k}"] = v
    if feature_store != "device":
        log(f"[serve_rgnn] feature store ({feature_store}): "
            f"{store.host_gathers} host gathers, "
            f"{store.bytes_moved / 1e6:.2f} MB moved"
            + (f", hit rate {store.hit_rate:.0%} "
               f"({store.evictions} evictions, {store.overflows} overflows)"
               if feature_store == "cached" else ""))
    log(f"[serve_rgnn] served {n} batches x {batch_size} seeds: "
        f"latency p50 {stats['latency_ms_p50']:.1f} ms / "
        f"p95 {stats['latency_ms_p95']:.1f} ms / "
        f"p99 {stats['latency_ms_p99']:.1f} ms "
        f"(wait {stats['wait_ms_mean']:.1f} + "
        f"compute {stats['compute_ms_mean']:.1f} ms avg), "
        f"throughput {stats['seeds_per_s']:.1f} seeds/s, "
        f"avg {stats['edges_per_batch']:.0f} sampled edges/batch")
    log(f"[serve_rgnn] executor: {executor.trace_count} traces / "
        f"{executor.cache_hits} cache hits "
        f"({retraces_after_warmup} retraces after warmup)"
        + "".join(f", {k.removesuffix('_hit_rate')} hit rate {v:.0%}"
                  for k, v in stats.items() if k.endswith("_hit_rate")))
    log(f"[serve_rgnn] sample predictions: {preds[:12].tolist()}")

    if profile and last_mb is not None:
        from repro.obs import profile as prof_mod
        p = engine.profile(params, last_mb, feats, warmup=1, iters=5) \
            if hasattr(engine, "profile") else \
            prof_mod.profile_minibatch(engine, params, last_mb, feats,
                                       warmup=1, iters=5)
        log("[serve_rgnn] per-op kernel breakdown (last batch):\n"
            + p.table())
        stats["profile"] = p.to_json()

    if sc is not None:
        if sc.tracer is not None:
            log("[serve_rgnn] phase table:\n" + sc.tracer.phase_table())
            if trace_out:
                sc.tracer.write(trace_out)
                log(f"[serve_rgnn] chrome trace -> {trace_out}")
        stats["metrics"] = sc.registry.snapshot()
        if metrics_out:
            sc.registry.export(metrics_out)
            log(f"[serve_rgnn] metrics snapshot -> {metrics_out}")
    return stats


def _serve_dist(engine, graph, store, params, batch_size, num_batches,
                repeat_after, warmup_batches, seed, skew, sc, metrics_out,
                log):
    """Multi-shard serving loop: route each request batch to its owner
    shards, sample per shard, run the one compiled ``shard_map`` step,
    report request-order predictions. Stats keys mirror the single-box
    loop so benchmarks/tests compare the two paths directly.

    The per-owner feature slabs are read through the feature store
    (``host_rows``), so with a host/cached store the full table never
    becomes device-resident — each shard holds only its owned rows."""
    cfg = engine.cfg
    log(f"[serve_rgnn] distributed: {cfg.num_partitions} shards over "
        f"{cfg.dp} devices\n" + engine.partition.describe())
    batcher = engine.dist_batcher
    serve_ex = engine.dist_serve_executor()
    own_feats = engine.shard_features(store)
    stream = SeedStream(graph.num_nodes, batch_size, seed=seed,
                        num_distinct=repeat_after, zipf_alpha=skew)

    lat, waits, computes, preds = [], [], [], None
    traces_at_warmup = None
    t_serve0 = time.perf_counter()
    for step in range(num_batches):
        if step == warmup_batches:
            traces_at_warmup = serve_ex.trace_count
        t0 = time.perf_counter()
        with obs.span("wait", batch=step):
            smb = batcher.build(stream.batch(step), step=step)
        t_wait = time.perf_counter() - t0
        t0 = time.perf_counter()
        with obs.span("execute", step=step):
            logits = serve_ex.run_minibatch(params, smb, own_feats)
            logits.block_until_ready()
        t_fwd = time.perf_counter() - t0
        lat.append(t_wait + t_fwd)
        waits.append(t_wait)
        computes.append(t_fwd)
        obs.metrics().histogram("serve_batch_ms").observe(
            (t_wait + t_fwd) * 1e3)
        preds = np.asarray(jnp.argmax(logits, axis=-1))
        log(f"[serve_rgnn] batch {step}: route+sample {t_wait*1e3:6.1f} ms, "
            f"forward {t_fwd*1e3:6.1f} ms")
    t_total = time.perf_counter() - t_serve0
    if traces_at_warmup is None:
        traces_at_warmup = serve_ex.trace_count

    lat_arr = np.asarray(lat)
    stats = {
        "batches": num_batches,
        "batch_size": batch_size,
        "dp": cfg.dp,
        "num_partitions": cfg.num_partitions,
        "latency_ms_p50": float(np.percentile(lat_arr, 50) * 1e3),
        "latency_ms_p95": float(np.percentile(lat_arr, 95) * 1e3),
        "latency_ms_p99": float(np.percentile(lat_arr, 99) * 1e3),
        "latency_ms_mean": float(lat_arr.mean() * 1e3),
        "wait_ms_mean": float(np.mean(waits) * 1e3),
        "compute_ms_mean": float(np.mean(computes) * 1e3),
        "seeds_per_s": batch_size * num_batches / max(t_total, 1e-9),
        "last_preds": preds,
        "warmup_batches": warmup_batches,
        "executor_traces": serve_ex.trace_count,
        "executor_cache_hits": serve_ex.cache_hits,
        "executor_compiled": serve_ex.num_compiled,
        "retraces_after_warmup": serve_ex.trace_count - traces_at_warmup,
        "host_builds": batcher.host_builds,
        "device_builds": 0,
        "sampler": "sharded",
    }
    for k, v in batcher.stats().items():
        stats[f"batcher_{k}"] = v
    for k, v in store.stats().items():
        stats[f"feature_{k}"] = v
    log(f"[serve_rgnn] served {num_batches} batches x {batch_size} seeds "
        f"on {cfg.num_partitions} shards / {cfg.dp} devices: "
        f"latency p50 {stats['latency_ms_p50']:.1f} ms "
        f"(route+sample {stats['wait_ms_mean']:.1f} + "
        f"compute {stats['compute_ms_mean']:.1f} ms avg), "
        f"{stats['retraces_after_warmup']} retraces after warmup")
    log(f"[serve_rgnn] sample predictions: {preds[:12].tolist()}")
    if sc is not None:
        stats["metrics"] = sc.registry.snapshot()
        if metrics_out:
            sc.registry.export(metrics_out)
    return stats


def serve_online(
    model: str = "rgat",
    dataset: str = "aifb",
    scale: float = 1.0,
    layers: int = 2,
    dim: int = 64,
    hidden: int = 64,
    classes: int = 16,
    fanouts=None,
    backend: str = "xla",
    tile: int = 32,
    node_block: int = 32,
    seed: int = 0,
    sampler: str = "host",
    feature_store: str = "device",
    feature_budget=None,
    skew=None,
    prefetch_depth: int = 2,
    cache_layouts: int = 64,
    rate_rps: float = 100.0,
    num_requests: int = 64,
    process: str = "poisson",
    burst_size: int = 4,
    slo_ms=1000.0,
    size_choices=(1, 2, 4, 8),
    max_batch: int = 32,
    max_wait_ms: float = 5.0,
    ladder_kind: str = "fine",
    speedup: float = 1.0,
    obs_mode: str = "on",
    trace_out=None,
    metrics_out=None,
    log=print,
):
    """Online serving: open-loop request traffic through the async
    ``ServingRuntime`` (deadline-aware coalescing, prefetch-overlapped
    execution) instead of the offline batch loop. Returns the runtime's
    stats dict — per-request latency percentiles, SLO attainment, queue
    depth, rung occupancy, and the zero-retrace counters."""
    from repro.serve import OpenLoopLoad, ServingRuntime, ladder

    with contextlib.ExitStack() as stack:
        sc = None
        if obs_mode == "off":
            stack.enter_context(obs.disabled())
        else:
            sc = stack.enter_context(obs.scope(
                metrics=True, tracing=trace_out is not None))

        t0 = time.perf_counter()
        graph = table3_graph(dataset, scale=scale, seed=seed)
        rng = np.random.default_rng(seed)
        feats = rng.normal(size=(graph.num_nodes, dim)).astype(np.float32)
        engine = hector.compile(
            model, graph, layers=layers, dim=dim, hidden=hidden,
            classes=classes, sample=fanouts, backend=backend, tile=tile,
            node_block=node_block, bucket=True, seed=seed, sampler=sampler,
            feature_store=feature_store, feature_budget=feature_budget,
            tune_full_graph=False, log=log)
        params = engine.init(jax.random.key(seed))
        store = engine.make_feature_store(feats)
        rungs = ladder(max_batch, ladder_kind)
        log(f"[serve_rgnn] online: {model} on {dataset} (scale {scale}), "
            f"ladder {rungs}, {rate_rps:g} req/s x {num_requests} "
            f"({process}), SLO {slo_ms} ms "
            f"(setup {time.perf_counter() - t0:.2f}s)")

        rt = ServingRuntime(
            engine, params, store, name=model, rungs=rungs,
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            depth=prefetch_depth, cache_layouts=cache_layouts)
        try:
            rt.calibrate(log=log)
            load = OpenLoopLoad(
                graph.num_nodes, rate_rps=rate_rps,
                num_requests=num_requests, process=process,
                burst_size=burst_size, size_choices=size_choices,
                slo_ms=slo_ms, zipf_alpha=skew, seed=seed)
            t_load0 = time.perf_counter()
            submitted = load.replay(rt.submit, speedup=speedup)
            rt.drain()
            t_load = time.perf_counter() - t_load0
        finally:
            rt.close()

        stats = rt.stats()
        stats["submitted"] = submitted
        stats["requests_per_s"] = submitted / max(t_load, 1e-9)
        log(f"[serve_rgnn] online: {submitted} requests in {t_load:.2f}s "
            f"({stats['requests_per_s']:.1f} req/s): "
            f"latency p50 {stats['latency_ms_p50']:.1f} ms / "
            f"p99 {stats['latency_ms_p99']:.1f} ms, "
            f"SLO attainment {stats['slo_attainment']:.1%}, "
            f"queue depth max {stats['queue_depth_max']}, "
            f"{stats['batches']} batches "
            f"(fill {stats['batch_fill']:.0%}, rungs {stats['rung_counts']})")
        log(f"[serve_rgnn] online executor: {stats['executor_traces']} "
            f"traces, {stats['retraces_after_warmup']} retraces after "
            f"warmup, {stats['shape_floor_growths']} shape-floor growths")
        if sc is not None:
            if sc.tracer is not None and trace_out:
                sc.tracer.write(trace_out)
                log(f"[serve_rgnn] chrome trace -> {trace_out}")
            stats["metrics"] = sc.registry.snapshot()
            if metrics_out:
                sc.registry.export(metrics_out)
                log(f"[serve_rgnn] metrics snapshot -> {metrics_out}")
        return stats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--runtime", default="loop", choices=["loop", "online"],
                    help="'loop': offline batch loop over a seed stream; "
                         "'online': open-loop request traffic through the "
                         "async serving runtime (deadline-aware coalescing, "
                         "per-request SLOs)")
    ap.add_argument("--model", default="rgat", choices=sorted(MODEL_PROGRAMS))
    ap.add_argument("--dataset", default="aifb",
                    choices=sorted(REDUCED_SCALES))
    ap.add_argument("--reduced", action="store_true",
                    help="scale the dataset for CPU tractability")
    ap.add_argument("--scale", type=float, default=None,
                    help="explicit dataset scale factor (overrides --reduced)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--fanout", default="5",
                    help="per-hop fanout, e.g. '5' or '5,10'; -1 = full")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-batches", type=int, default=8)
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "pallas_interpret"])
    ap.add_argument("--tile", type=int, default=32)
    ap.add_argument("--node-block", type=int, default=32)
    ap.add_argument("--no-bucket", action="store_true",
                    help="disable power-of-two shape bucketing (each batch "
                         "then compiles fresh shapes)")
    ap.add_argument("--sampler", default="host", choices=["host", "device"],
                    help="'host': NumPy fanout sampling + host layout "
                         "build; 'device': jit-compiled sampling + layout "
                         "over a device-resident CSC (equivalent block "
                         "streams under one seed)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel device count: shard the graph and "
                         "serve every request batch across all shards in "
                         "one compiled shard_map step")
    ap.add_argument("--partitions", type=int, default=None,
                    help="graph shard count (default: one per --dp device; "
                         "a multiple of --dp folds extra shards onto "
                         "devices with bit-identical results)")
    ap.add_argument("--feature-store", default="device",
                    choices=["device", "host", "cached"],
                    help="where the node-feature table lives: 'device' = "
                         "full table device-resident; 'host' = host-"
                         "resident per-ntype tables, only sampled rows "
                         "shipped (inside the prefetch overlap); 'cached' "
                         "= host tier + fixed-budget device hot-row cache. "
                         "Predictions are bitwise identical across all "
                         "three")
    ap.add_argument("--feature-budget", type=int, default=None,
                    help="device hot-row count for --feature-store cached "
                         "(default: num_nodes / 4); per-ntype split is "
                         "measured from probe traffic")
    ap.add_argument("--skew", type=float, default=None, metavar="ALPHA",
                    help="Zipf exponent for the seed stream (power-law "
                         "traffic; popularity rank r drawn with p ~ "
                         "(r+1)^-ALPHA). Default: uniform")
    ap.add_argument("--cache-blocks", type=int, default=0,
                    help="LRU capacity of the sampled-block cache keyed by "
                         "(seeds, fanout); 0 disables")
    ap.add_argument("--cache-layouts", type=int, default=0,
                    help="LRU capacity of the KernelLayouts cache keyed by "
                         "block signature; 0 disables")
    ap.add_argument("--repeat-after", type=int, default=4,
                    help="wrap the seed stream onto N distinct batches "
                         "(models power-law repeat traffic — the production "
                         "serving assumption; every distinct batch compiles "
                         "during warmup, so steady state retraces zero "
                         "times). 0 = fresh random seeds every batch")
    ap.add_argument("--eager", action="store_true",
                    help="bypass the whole-plan compiled executor (op-by-op "
                         "debug path)")
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
                    help="after serving, time every op instance of the "
                         "compiled plan individually (per-op kernel "
                         "breakdown on the last batch)")
    online = ap.add_argument_group("online runtime (--runtime online)")
    online.add_argument("--rate", type=float, default=100.0,
                        help="average request arrival rate (req/s)")
    online.add_argument("--requests", type=int, default=64,
                        help="number of requests to replay")
    online.add_argument("--arrivals", default="poisson",
                        choices=["poisson", "burst", "uniform"],
                        help="arrival process (open loop: arrivals never "
                             "wait on completions)")
    online.add_argument("--burst-size", type=int, default=4,
                        help="requests per burst for --arrivals burst")
    online.add_argument("--slo-ms", type=float, default=1000.0,
                        help="per-request latency budget; admission "
                             "rejects requests that cannot make it")
    online.add_argument("--sizes", default="1,2,4,8",
                        help="comma-separated request sizes (seeds per "
                             "request)")
    online.add_argument("--max-wait-ms", type=float, default=5.0,
                        help="coalescer hold time before dispatching a "
                             "partial batch")
    online.add_argument("--ladder", default="fine",
                        choices=["fine", "pow2"],
                        help="batch-size rung ladder: 'fine' = {2^k, "
                             "3*2^k} validated against measured latency, "
                             "'pow2' = powers of two only")
    online.add_argument("--speedup", type=float, default=1.0,
                        help="compress the arrival schedule by this factor")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.scale is not None:
        scale = args.scale
    elif args.reduced:
        scale = REDUCED_SCALES[args.dataset]
    else:
        scale = 1.0
    if args.runtime == "online":
        return serve_online(
            model=args.model, dataset=args.dataset, scale=scale,
            layers=args.layers, dim=args.dim, hidden=args.hidden,
            classes=args.classes,
            fanouts=parse_fanout(args.fanout, args.layers),
            backend=args.backend, tile=args.tile,
            node_block=args.node_block, seed=args.seed,
            sampler=args.sampler, feature_store=args.feature_store,
            feature_budget=args.feature_budget, skew=args.skew,
            cache_layouts=args.cache_layouts or 64,
            rate_rps=args.rate, num_requests=args.requests,
            process=args.arrivals, burst_size=args.burst_size,
            slo_ms=args.slo_ms,
            size_choices=tuple(int(s) for s in args.sizes.split(",")),
            max_batch=args.batch_size, max_wait_ms=args.max_wait_ms,
            ladder_kind=args.ladder, speedup=args.speedup,
            obs_mode=args.obs, metrics_out=args.metrics_out,
        )
    return serve(
        model=args.model, dataset=args.dataset, scale=scale,
        layers=args.layers, dim=args.dim, hidden=args.hidden,
        classes=args.classes,
        fanouts=parse_fanout(args.fanout, args.layers),
        batch_size=args.batch_size, num_batches=args.num_batches,
        backend=args.backend, tile=args.tile, node_block=args.node_block,
        bucket=not args.no_bucket, seed=args.seed, sampler=args.sampler,
        dp=args.dp, partitions=args.partitions,
        feature_store=args.feature_store,
        feature_budget=args.feature_budget, skew=args.skew,
        cache_blocks=args.cache_blocks, cache_layouts=args.cache_layouts,
        repeat_after=args.repeat_after or None, compiled=not args.eager,
        tune=args.tune, tune_cache=args.tune_cache,
        obs_mode=args.obs, trace_out=args.trace_out,
        metrics_out=args.metrics_out, profile=args.profile,
    )


if __name__ == "__main__":
    main()
