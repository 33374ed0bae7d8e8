#!/usr/bin/env python3
"""Drive the RGNN main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the partitioned path only

One chip, in one process, through the entry points a user calls
(``hector.compile`` -> ``RGNNEngine`` -> block executors -> kernels):

1. kernels: every Pallas kernel of the main path (segment GEMM, its
   gather-fused form, the backward outer product, the five traversal
   kernels, ``candidate_keys``) at d=64, tile=128, node_block=128 against
   the plain ``kernels/ref.py`` references;
2. serving: a 2-layer RGAT at the paper's width (dim = hidden = 64,
   16 classes) on the am-shaped graph at published scale
   (``table3_graph("am", 1.0)``: 1.9M nodes, 5.7M edges, 108 relation
   types), batches of 64 seeds with fanout 10 per hop, tile = node_block =
   128. The same batches run once with ``backend="xla"`` and once with
   ``backend="pallas"``; the two backends' logits must agree within
   ``LOGITS_TOL``;
3. training: sampled SGD steps through the compiled ``BlockTrainExecutor``
   on the Pallas path; every loss must be finite.

``--four-chips`` runs only the partitioned path: P=4 edge-cut shards served
and trained at dp=4 (one shard per chip, inputs placed on the data mesh)
against dp=1 over the same four shards, and reports whether the two agree
bit for bit.

Float32 matmuls run at precision "highest" (``PRECISION``): the TPU's
default float32 matmul is a single bfloat16 pass, which would hide kernel
errors below about 1e-2. Weights are random, from ``SEED``.

Prints device info, timings and counts, then as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. Starts no child process.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
MODEL = dict(layers=2, dim=64, hidden=64, classes=16)
GRAPH = ("am", 1.0)
BATCH, FANOUT, TILE = 64, 10, 128
WARMUP, STEPS = 2, 8          # batches/steps before and inside the window
DIST_STEPS = 4                # serve+train steps per mode on four chips,
#                               over WARMUP distinct batches
PRECISION = "highest"
LOGITS_TOL = 1e-4             # pallas vs xla logits, absolute and relative
KERNEL_TOL = 1e-4             # each kernel vs its kernels/ref.py reference


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    """The devices to run on; exits (no result printed) without a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, JAX finds "
                 f"{len(devices)}")
    return devices


def _close(name, got, want, tol):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=name)
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def check_kernels(backend: str = "pallas", d: int = 64, tile: int = 128):
    """Each Pallas kernel (forward, and the backward ones through
    ``jax.grad``) against its plain ``jnp`` reference."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels import layout as L, ops, ref as R
    from repro.kernels import sampling_ops as SO

    rng = np.random.default_rng(SEED)
    errs = {}

    # typed-segment GEMM, plain and gather-fused, with the fused row scale;
    # the backward runs the GEMM on dY and the outer-product kernel for dW
    groups = 8
    sizes = rng.integers(0, 3 * tile, groups)
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    seg = jnp.asarray(np.repeat(np.arange(groups), sizes))
    m, n_src = int(ptr[-1]), 500
    ps = L.pad_segments(ptr, tile)
    lay = ops.padded_segments_dev(ps)
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(groups, d, d)) / 8, jnp.float32)
    scale = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    src = jnp.asarray(rng.normal(size=(n_src, d)), jnp.float32)
    gidx = rng.integers(0, n_src, m).astype(np.int32)
    gmap = jnp.asarray(L.compose_gather_rows(ps, gidx))

    def mm(x, w, s):
        return ops.segment_mm(x, w, lay, row_scale=s, backend=backend)

    def mm_ref(x, w, s):
        return R.segment_mm_ref(x, w, seg, s)

    def gmm(x, w, s):
        return ops.segment_mm_gather(x, w, lay, gmap, row_scale=s,
                                     backend=backend)

    def gmm_ref(x, w, s):
        return R.gather_mm_ref(x, w, jnp.asarray(gidx), seg, s)

    for name, f, f_ref, a in (("segment_mm", mm, mm_ref, x),
                              ("segment_mm_gather", gmm, gmm_ref, src)):
        errs[name] = _close(name, jax.jit(f)(a, w, scale), f_ref(a, w, scale),
                            KERNEL_TOL)
        g = jax.jit(jax.grad(lambda *p: jnp.sum(jnp.sin(f(*p))),
                             argnums=(0, 1, 2)))(a, w, scale)
        g_ref = jax.grad(lambda *p: jnp.sum(jnp.sin(f_ref(*p))),
                         argnums=(0, 1, 2))(a, w, scale)
        errs[name + " grads"] = max(
            _close(f"{name} grad {i}", u, v, KERNEL_TOL)
            for i, (u, v) in enumerate(zip(g, g_ref)))

    # traversal: edge softmax + aggregation and weighted aggregation, each
    # with the message gather fused into the kernel and materialized
    n_nodes, n_edges, n_rows = 700, 6000, 900
    canon = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    perm = np.argsort(canon, kind="stable").astype(np.int32)
    dptr = np.concatenate([[0], np.cumsum(np.bincount(canon,
                                                      minlength=n_nodes))])
    bc = ops.blocked_csr_dev(L.block_csr(dptr, tile, tile), perm)
    dst = jnp.asarray(canon)
    scores = jnp.asarray(rng.normal(size=(n_edges,)), jnp.float32)
    wts = jnp.asarray(rng.normal(size=(n_edges,)), jnp.float32)
    msg = jnp.asarray(rng.normal(size=(n_edges, d)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, n_rows, n_edges).astype(np.int32))
    for fuse in (True, False):
        for label, m_in, m_rows, m_e in (("edge", msg, None, msg),
                                         ("compact", table, rows,
                                          table[rows])):
            tag = f"{label}, {'fused' if fuse else 'materialized'} gather"
            out = jax.jit(lambda s, mm_: ops.edge_softmax_agg(
                s, mm_, dst, n_nodes, bc=bc, backend=backend,
                msg_rows=m_rows, fuse_gather=fuse))(scores, m_in)
            errs[f"softmax_agg ({tag})"] = _close(
                tag, out, R.softmax_agg_ref(scores, m_e, dst, n_nodes),
                KERNEL_TOL)
            out = jax.jit(lambda s, mm_: ops.weighted_agg(
                s, mm_, dst, n_nodes, bc=bc, backend=backend,
                msg_rows=m_rows, fuse_gather=fuse))(wts, m_in)
            errs[f"weighted_agg ({tag})"] = _close(
                tag, out, R.weighted_agg_ref(wts, m_e, dst, n_nodes),
                KERNEL_TOL)

    # sampling keys: the kernel's keys equal the XLA formulation's exactly
    starts = jnp.asarray(rng.integers(0, 10**6, (64, 108)), jnp.int32)
    cnts = jnp.asarray(rng.integers(0, 40, (64, 108)), jnp.int32)
    k_ker = SO.candidate_keys(starts, cnts, 12345, 32, backend)
    k_xla = SO.candidate_keys(starts, cnts, 12345, 32, "xla")
    if not bool(jnp.all(k_ker == k_xla)):
        raise AssertionError("candidate_keys: kernel keys differ from XLA")
    errs["candidate_keys"] = 0.0
    for name, err in errs.items():
        log(f"[kernels] {name}: max |err| {err:.3e} (tol {KERNEL_TOL:g})")
    return errs


def make_inputs(dataset: str, scale: float):
    """The graph, its node features (host, float32) and labels."""
    import numpy as np
    from repro.core.graph import table3_graph
    t0 = time.perf_counter()
    graph = table3_graph(dataset, scale=scale, seed=SEED)
    rng = np.random.default_rng(SEED)
    feats = rng.standard_normal((graph.num_nodes, MODEL["dim"]),
                                dtype=np.float32)
    labels = rng.integers(0, MODEL["classes"], graph.num_nodes)
    log(f"[graph] {dataset} x{scale}: {graph.num_nodes} nodes, "
        f"{graph.num_edges} edges, {graph.num_etypes} relation types, "
        f"features {feats.nbytes / 1e9:.3f} GB; built in "
        f"{time.perf_counter() - t0:.2f} s")
    return graph, feats, labels


def compile_engine(graph, backend: str, **kw):
    import hector
    t0 = time.perf_counter()
    engine = hector.compile("rgat", graph, sample=FANOUT, backend=backend,
                            tile=TILE, node_block=TILE, seed=SEED,
                            **MODEL, **kw)
    log(f"[compile] {engine!r} in {time.perf_counter() - t0:.2f} s")
    return engine


def serve(engine, params, store, batches, name: str):
    """Serve the batches; per-batch logits on the host."""
    import numpy as np
    ex = engine.block_executor
    outs, times, traces_at_warmup = [], [], None
    for i, mb in enumerate(batches):
        if i == WARMUP:
            traces_at_warmup = ex.trace_count
        t0 = time.perf_counter()
        logits = engine.apply_blocks(params, mb, store)
        logits.block_until_ready()
        times.append(time.perf_counter() - t0)
        out = np.asarray(logits)
        if out.shape != (len(mb.seq.seeds), MODEL["classes"]):
            raise AssertionError(f"serve[{name}]: logits shape {out.shape}")
        if not np.isfinite(out).all():
            raise AssertionError(f"serve[{name}]: non-finite logits")
        outs.append(out)
    window = np.asarray(times[WARMUP:]) * 1e3
    log(f"[serve {name}] {len(window)} batches x {BATCH} seeds after "
        f"{WARMUP} warm-up: first batch {times[0] * 1e3:.1f} ms (compile "
        f"included), window p50 {np.percentile(window, 50):.2f} ms, max "
        f"{window.max():.2f} ms; {ex.trace_count} traces, "
        f"{ex.trace_count - traces_at_warmup} after warm-up")
    return outs


def run_one_chip(backend: str = "pallas", dataset: str = GRAPH[0],
                 scale: float = GRAPH[1]) -> None:
    """Kernels, serving on both backends, and training, on one device."""
    import numpy as np
    import jax
    from repro.optim import AdamW
    from repro.sampling import SeedStream
    from repro.sampling.bucketing import ShapeFloors

    check_kernels(backend)
    graph, feats, labels = make_inputs(dataset, scale)
    ref = compile_engine(graph, "xla")
    ker = compile_engine(graph, backend)
    params = ref.init(SEED)
    t0 = time.perf_counter()
    store = ref.make_feature_store(feats)
    jax.block_until_ready(store.full_table())
    log(f"[features] device table in {time.perf_counter() - t0:.2f} s")

    # one set of batches for both backends; grow-only shape floors let the
    # bucketed shapes settle, so the window replays compiled programs
    loader = ref.make_loader(SeedStream(graph.num_nodes, BATCH, seed=SEED),
                             num_batches=WARMUP + STEPS,
                             shape_floors=ShapeFloors())
    try:
        batches = list(loader)
    finally:
        loader.close()
    hops = [[b.num_src for b in mb.seq.blocks] for mb in batches]
    log(f"[batches] {len(batches)} sampled; block nodes per hop {hops}")
    out_ref = serve(ref, params, store, batches, "xla")
    out_ker = serve(ker, params, store, batches, backend)
    mb = batches[0]
    lowered = jax.jit(ker.block_executor.hector_blocks).lower(
        list(params), list(mb.tensors), list(mb.layouts),
        list(mb.dst_locals), mb.seed_perm,
        {"feature": jax.ShapeDtypeStruct((mb.input_ids.shape[0],
                                          MODEL["dim"]), np.float32)})
    n_kernels = lowered.as_text().count("tpu_custom_call")
    log(f"[serve {backend}] Pallas kernel calls in the serve program: "
        f"{n_kernels}")
    if backend == "pallas" and n_kernels == 0:
        raise AssertionError("the pallas serve program holds no kernel")
    err = max(_close(f"batch {i}", a, b, LOGITS_TOL)
              for i, (a, b) in enumerate(zip(out_ker, out_ref)))
    log(f"[serve] {backend} vs xla logits over {len(batches)} batches: max "
        f"|diff| {err:.3e} (tol {LOGITS_TOL:g}, precision {PRECISION})")

    opt = AdamW(learning_rate=3e-3)
    state = ker.init_state(params, opt)
    ex = ker.train_executor(opt)
    loader = ker.make_loader(
        SeedStream(graph.num_nodes, BATCH, seed=SEED + 1),
        num_batches=WARMUP + STEPS, feature_store=store,
        shape_floors=ShapeFloors())
    losses, times = [], []
    traces_at_warmup = None
    try:
        for mb in loader:
            if len(losses) == WARMUP:
                traces_at_warmup = ex.trace_count
            t0 = time.perf_counter()
            state, metrics = ker.train_step(
                state, mb, mb.seq.slice_labels(labels), store)
            losses.append(float(metrics["loss"]))
            times.append(time.perf_counter() - t0)
    finally:
        loader.close()
    if len(losses) != WARMUP + STEPS or not np.isfinite(losses).all():
        raise AssertionError(f"train: losses {losses}")
    window = np.asarray(times[WARMUP:]) * 1e3
    log(f"[train {backend}] {len(losses)} steps, losses "
        f"{[round(x, 4) for x in losses]}; first step {times[0] * 1e3:.1f} "
        f"ms (compile included), window p50 "
        f"{np.percentile(window, 50):.2f} ms; {ex.trace_count} traces, "
        f"{ex.trace_count - traces_at_warmup} after warm-up")


def run_four_chips(backend: str = "pallas", dataset: str = GRAPH[0],
                   scale: float = GRAPH[1]) -> bool:
    """P=4 partitioned serving and training at dp=4 against dp=1 over the
    same shards and batches; returns whether they agree bit for bit."""
    import numpy as np
    import jax
    from repro.optim import AdamW
    from repro.sampling import SeedStream

    graph, feats, labels = make_inputs(dataset, scale)
    engines = {dp: compile_engine(graph, backend, dp=dp, partitions=4)
               for dp in (1, 4)}
    log(engines[4].partition.describe())
    params = engines[1].init(SEED)
    stream = SeedStream(graph.num_nodes, BATCH, seed=SEED)
    batcher = engines[4].dist_batcher
    # repeat traffic: WARMUP distinct batches, so each mode compiles its
    # steps once and the later steps replay them
    batches = [batcher.build(stream.batch(i % WARMUP), step=i)
               for i in range(DIST_STEPS)]
    opt = AdamW(learning_rate=3e-3)
    results = {}
    for dp, engine in engines.items():
        own = engine.shard_features(feats)
        log(f"[dp={dp}] feature slabs {own.shape} on "
            f"{sorted({d.id for d in own.sharding.device_set})}")
        serve_ex = engine.dist_serve_executor()
        train_ex = engine.dist_train_executor(opt)
        state = opt.init(params)
        logits, losses, times = [], [], []
        for smb in batches:
            t0 = time.perf_counter()
            out = serve_ex.run_minibatch(params, smb, own)
            logits.append(np.asarray(out))
            state, m = train_ex.grad_and_update(state, smb, labels, own)
            losses.append(float(m["loss"]))
            times.append(time.perf_counter() - t0)
        if not (np.isfinite(losses).all()
                and all(np.isfinite(x).all() for x in logits)):
            raise AssertionError(f"dp={dp}: non-finite outputs")
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            (state.params, state.mu, state.nu))]
        window = np.asarray(times[WARMUP:]) * 1e3
        log(f"[dp={dp}] {len(batches)} serve+train steps: first "
            f"{times[0] * 1e3:.1f} ms (compile included), window p50 "
            f"{np.percentile(window, 50):.2f} ms; losses "
            f"{[round(x, 4) for x in losses]}")
        results[dp] = (logits, losses, leaves)
    (l1, s1, p1), (l4, s4, p4) = results[1], results[4]
    same_logits = all((a == b).all() for a, b in zip(l1, l4))
    same_state = all((a == b).all() for a, b in zip(p1, p4))
    bitwise = same_logits and s1 == s4 and same_state
    log(f"[dist] dp=4 vs dp=1 over 4 shards: logits "
        f"{'equal' if same_logits else 'differ'}, losses "
        f"{'equal' if s1 == s4 else 'differ'}, optimizer state "
        f"{'equal' if same_state else 'differ'} -> bitwise "
        f"{'holds' if bitwise else 'does not hold'}")
    if not bitwise:
        diff = max(float(np.max(np.abs(a - b))) for a, b in zip(l1, l4))
        log(f"[dist] max |logit diff| {diff:.3e}")
        _close("dp=4 vs dp=1 logits", np.concatenate(l4),
               np.concatenate(l1), LOGITS_TOL)
    return bitwise


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned dp=4 vs dp=1 path on "
                         "four chips")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devices = require_tpu(count)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_default_matmul_precision", PRECISION)
    dev = devices[0]
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {cache}; matmul precision "
        f"{PRECISION}")
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips()
    else:
        run_one_chip()
    log(f"[done] {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
