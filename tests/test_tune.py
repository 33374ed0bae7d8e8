"""Autotuned operator variants (ISSUE 4): every point of the tuning space
must match the ``kernels/ref.py`` oracles (outputs AND gradients); the
persistent cache must replay decisions with zero measurements; per-var
materialization, the device-derived VMEM budget, and the decision-table
fingerprint in the executor compile cache all get pinned here."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.graph import synthetic_heterograph
from repro.core.ir import passes
from repro.core.ir import inter_op as I
from repro.core.module import HectorModule
from repro.kernels import layout as L, ops, ref as R
from repro.models import rgat_program
from repro.tune import cost, space
from repro.tune.cache import TuneCache
from repro.tune.decisions import TuningDecisions
from repro.tune.device import BUDGET_ENV, fused_gather_budget_bytes
from repro.tune.tuner import Tuner, _KeyRecorder

BACKENDS = ["xla", "pallas_interpret"]


# ---------------------------------------------------------------------------
# op-level: the full variant space vs the ref oracles
# ---------------------------------------------------------------------------
def _segments(rng, n_groups, max_size):
    sizes = rng.integers(1, max_size, n_groups)
    ptr = np.zeros(n_groups + 1, np.int64)
    np.cumsum(sizes, out=ptr[1:])
    seg_ids = np.repeat(np.arange(n_groups), sizes)
    return ptr, seg_ids, int(sizes.sum())


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("tile_rows", [None, 8])     # None = layout tile (16)
@pytest.mark.parametrize("tile_n", [128, 8])
def test_segment_mm_variant_space(rng, backend, tile_rows, tile_n):
    """Row sub-tiling x column tiling x backend == ref, values and grads."""
    ptr, seg_ids, m = _segments(rng, 4, 19)
    x = jnp.asarray(rng.normal(size=(m, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 12, 24)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    lay = ops.padded_segments_dev(L.pad_segments(ptr, 16))

    def f(x, w, s):
        return jnp.sum(jnp.sin(ops.segment_mm(
            x, w, lay, row_scale=s, backend=backend, tile_n=tile_n,
            tile_rows=tile_rows)))

    def f_ref(x, w, s):
        return jnp.sum(jnp.sin(R.segment_mm_ref(x, w, jnp.asarray(seg_ids),
                                                s)))

    y = ops.segment_mm(x, w, lay, row_scale=s, backend=backend,
                       tile_n=tile_n, tile_rows=tile_rows)
    np.testing.assert_allclose(y, R.segment_mm_ref(x, w, jnp.asarray(seg_ids),
                                                   s), rtol=1e-4, atol=1e-4)
    g = jax.grad(f, argnums=(0, 1, 2))(x, w, s)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, s)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("tile_rows", [None, 8])
@pytest.mark.parametrize("tile_n", [128, 8])
def test_segment_mm_gather_variant_space(rng, backend, tile_rows, tile_n):
    """The in-kernel-gather GEMM across the tile space == ref."""
    ptr, seg_ids, m = _segments(rng, 4, 17)
    n_src = 11
    gidx = rng.integers(0, n_src, m)
    feats = jnp.asarray(rng.normal(size=(n_src, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 12, 24)), jnp.float32)
    ps = L.pad_segments(ptr, 16)
    lay = ops.padded_segments_dev(ps)
    gmap = jnp.asarray(L.compose_gather_rows(ps, gidx))

    def f(feats, w):
        return jnp.sum(jnp.sin(ops.segment_mm_gather(
            feats, w, lay, gmap, backend=backend, tile_n=tile_n,
            tile_rows=tile_rows)))

    def f_ref(feats, w):
        return jnp.sum(jnp.sin(R.gather_mm_ref(
            feats, w, jnp.asarray(gidx), jnp.asarray(seg_ids))))

    y = ops.segment_mm_gather(feats, w, lay, gmap, backend=backend,
                              tile_n=tile_n, tile_rows=tile_rows)
    np.testing.assert_allclose(
        y, R.gather_mm_ref(feats, w, jnp.asarray(gidx),
                           jnp.asarray(seg_ids)), rtol=1e-4, atol=1e-4)
    g = jax.grad(f, argnums=(0, 1))(feats, w)
    g_ref = jax.grad(f_ref, argnums=(0, 1))(feats, w)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# plan-level: forced decisions over the whole space == the default lowering
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def graph():
    return synthetic_heterograph(num_nodes=96, num_edges=700, num_ntypes=3,
                                 num_etypes=5, seed=0,
                                 target_compaction=0.5)


@pytest.fixture(scope="module")
def feats(graph):
    rng = np.random.default_rng(1)
    return jnp.asarray(rng.normal(size=(graph.num_nodes, 16)), jnp.float32)


def _recorded_keys(mod, params, feats):
    rec = _KeyRecorder()
    from repro.core import codegen
    jax.eval_shape(lambda p, f: codegen.execute_plan(
        mod.plan, p, mod.gt, f, mod.layouts, mod.backend, rec),
        params, {"feature": feats})
    return rec.keys


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("compact_vars", [frozenset(), None])  # none/all
@pytest.mark.parametrize("variant_kw", [
    {},                                            # defaults
    {"tile_rows": 8},
    {"fuse_gather": True},
    {"fuse_gather": False},
    {"tile_rows": 8, "tile_n": 8, "fuse_gather": True},
])
def test_plan_decisions_match_reference(graph, feats, backend, compact_vars,
                                        variant_kw):
    """Force one variant onto EVERY op of an RGAT plan (each materialization
    choice) and check outputs + gradients against the default xla lowering
    (itself pinned to the vanilla baselines in test_models_rgnn)."""
    prog = rgat_program(16, 24)
    ref_mod = HectorModule(prog, graph, backend="xla", tile=16, node_block=16)
    params = ref_mod.init(jax.random.key(0))
    want = ref_mod.apply(params, {"feature": feats})["h_out"]
    g_ref = jax.grad(lambda p: jnp.sum(
        ref_mod.apply(p, {"feature": feats})["h_out"] ** 2))(params)

    mod = HectorModule(prog, graph, backend=backend, tile=16, node_block=16,
                       compact_vars=compact_vars, jit=False)
    decisions = TuningDecisions()
    for key in _recorded_keys(mod, params, feats):
        if key.startswith("gemm"):
            decisions.set_op(key, space.GemmVariant(**variant_kw))
        else:
            decisions.set_op(key, space.TravVariant(
                fuse_gather=variant_kw.get("fuse_gather")))
    mod.decisions = decisions

    got = mod.apply(params, {"feature": feats})["h_out"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    g = jax.grad(lambda p: jnp.sum(
        mod.apply(p, {"feature": feats})["h_out"] ** 2))(params)
    for k in g_ref:
        denom = float(jnp.max(jnp.abs(g_ref[k]))) + 1e-9
        np.testing.assert_allclose(np.asarray(g[k]) / denom,
                                   np.asarray(g_ref[k]) / denom,
                                   rtol=5e-4, atol=5e-4)


def test_op_backend_override_dispatches(graph, feats):
    """A per-op backend decision actually changes the executed kernel: an
    'xla'-planned module with every op forced to 'pallas_interpret' still
    matches, and vice versa."""
    prog = rgat_program(16, 24)
    mod = HectorModule(prog, graph, backend="xla", tile=16, node_block=16,
                       jit=False)
    params = mod.init(jax.random.key(0))
    want = mod.apply(params, {"feature": feats})["h_out"]
    decisions = TuningDecisions()
    for key in _recorded_keys(mod, params, feats):
        if key.startswith("gemm"):
            decisions.set_op(key, space.GemmVariant(
                backend="pallas_interpret"))
        else:
            decisions.set_op(key, space.TravVariant(
                backend="pallas_interpret"))
    mod.decisions = decisions
    got = mod.apply(params, {"feature": feats})["h_out"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# per-var materialization
# ---------------------------------------------------------------------------
def test_lower_program_per_var_materialization():
    prog = rgat_program(16, 24)
    cands = passes.compactable_edge_vars(prog)
    assert cands, "rgat must expose at least one compactable edge var"
    # subset: only the first var compact
    plan = passes.lower_program(prog, compact_vars=frozenset(cands[:1]))
    compact = {v for v, l in plan.layouts.items() if l == I.Layout.COMPACT}
    assert compact <= set(cands[:1])
    # empty set == vanilla everywhere, even with compact=True default
    plan_v = passes.lower_program(prog, compact=True,
                                  compact_vars=frozenset())
    assert not any(l == I.Layout.COMPACT for l in plan_v.layouts.values())
    # None keeps the static all-eligible policy
    plan_c = passes.lower_program(prog, compact=True, compact_vars=None)
    assert any(l == I.Layout.COMPACT for l in plan_c.layouts.values())


# ---------------------------------------------------------------------------
# VMEM budget (satellite: index bytes counted, device-derived budget)
# ---------------------------------------------------------------------------
def test_fits_vmem_counts_index_bytes(monkeypatch):
    """The source block counts against the VMEM budget (lane-padded,
    double-buffered); the scalar-prefetched index map against SMEM, apart."""
    from repro.core import codegen
    src = jnp.zeros((100, 10), jnp.float32)      # 2 * 104 * 128 * 4 bytes
    monkeypatch.setenv(BUDGET_ENV, str(2 * 104 * 128 * 4))
    assert codegen._gather_fits(src)
    assert codegen._gather_fits(src, None)         # absent maps are free
    assert codegen._gather_fits(src, jnp.zeros((1000,), jnp.int32), 128)
    monkeypatch.setenv(BUDGET_ENV, str(2 * 104 * 128 * 4 - 1))
    assert not codegen._gather_fits(src)
    # 200k slots (800 kB + tile table) exceed half of the 1 MiB SMEM: no
    # VMEM budget, however large, makes that map fit
    big_map = jnp.zeros((200_000,), jnp.int32)
    monkeypatch.setenv(BUDGET_ENV, str(10**12))
    assert codegen._gather_fits(src, big_map[:100_000], 128)
    assert not codegen._gather_fits(src, big_map, 128)
    # the in-kernel row gather moves 32-bit rows only
    assert not codegen._gather_fits(src.astype(jnp.bfloat16))


def test_vmem_budget_is_device_derived(monkeypatch):
    from repro.tune import device as D
    monkeypatch.delenv(BUDGET_ENV, raising=False)
    monkeypatch.delenv("REPRO_VMEM_BYTES", raising=False)
    # the CPU models a v5e: 128 MiB VMEM and 1 MiB SMEM, as its compiler
    # reports them
    assert D.vmem_bytes() == 128 * 1024 * 1024
    assert D.smem_bytes() == 1024 * 1024
    assert fused_gather_budget_bytes() == D.vmem_bytes() // 4
    monkeypatch.setenv("REPRO_VMEM_BYTES", str(8 * 1024 * 1024))
    assert fused_gather_budget_bytes() == 2 * 1024 * 1024


def test_unknown_tpu_kind_raises(monkeypatch):
    """A TPU kind missing from the memory table is an error, not a
    default size."""
    import types
    from repro.tune import device as D

    def fake(kind):
        return lambda: [types.SimpleNamespace(platform="tpu",
                                              device_kind=kind)]
    D.device_kind.cache_clear()
    try:
        monkeypatch.setattr(D.jax, "devices", fake("TPU v99"))
        with pytest.raises(RuntimeError, match="unknown TPU kind"):
            D.vmem_bytes()
        D.device_kind.cache_clear()
        monkeypatch.setattr(D.jax, "devices", fake("TPU v5 lite"))
        assert D.device_kind() == "tpu:TPU v5 lite"
        assert D.vmem_bytes() == 128 * 1024 * 1024
    finally:
        monkeypatch.undo()
        D.device_kind.cache_clear()


# ---------------------------------------------------------------------------
# keys / cost model
# ---------------------------------------------------------------------------
def test_key_roundtrip_and_candidates(graph, feats):
    prog = rgat_program(16, 24)
    mod = HectorModule(prog, graph, backend="xla", tile=16, node_block=16,
                       jit=False)
    params = mod.init(jax.random.key(0))
    keys = _recorded_keys(mod, params, feats)
    assert any(k.startswith("gemm") for k in keys)
    assert any(k.startswith("trav") for k in keys)
    for key in keys:
        info = space.parse_key(key)
        assert info["kind"] in ("gemm", "trav")
        cands = space.candidates_for_key(key, "xla")
        assert cands[0] in (space.GEMM_DEFAULT, space.TRAV_DEFAULT)
        pruned = cost.prune(key, cands, "xla", k=3)
        assert pruned[0] == cands[0] and len(pruned) <= 3
        for v in pruned:
            assert cost.score(key, v, "xla") < 1e9


# ---------------------------------------------------------------------------
# persistent cache: cold run measures, warm run replays
# ---------------------------------------------------------------------------
def test_tuner_persistent_cache_zero_remeasure(graph, tmp_path):
    cache = str(tmp_path / "tune.json")
    progs = [rgat_program(16, 24)]
    t1 = Tuner(mode="full", cache_path=cache, iters=1, warmup=0)
    rep1 = t1.tune_stack(progs, graph, backend="xla", tile=16, node_block=16,
                         feat_dims=[16])
    assert t1.stats["measurements"] > 0
    assert os.path.exists(cache)

    t2 = Tuner(mode="full", cache_path=cache, iters=1, warmup=0)
    rep2 = t2.tune_stack(progs, graph, backend="xla", tile=16, node_block=16,
                         feat_dims=[16])
    assert t2.stats["measurements"] == 0
    assert t2.stats["cache_hits"] > 0
    assert rep2.decisions.fingerprint() == rep1.decisions.fingerprint()
    assert (rep2.tile, rep2.node_block) == (rep1.tile, rep1.node_block)
    assert rep2.compact_vars == rep1.compact_vars

    # cached mode replays without measuring too
    t3 = Tuner(mode="cached", cache_path=cache)
    rep3 = t3.tune_stack(progs, graph, backend="xla", tile=16, node_block=16,
                         feat_dims=[16])
    assert t3.stats["measurements"] == 0
    assert rep3.decisions.fingerprint() == rep1.decisions.fingerprint()


def test_decisions_fingerprint_keys_executor_cache(graph, feats):
    """Swapping the decision table recompiles instead of reusing the stale
    executable (the fingerprint is part of the compile-cache key)."""
    prog = rgat_program(16, 24)
    mod = HectorModule(prog, graph, backend="xla", tile=16, node_block=16)
    params = mod.init(jax.random.key(0))
    mod.apply(params, {"feature": feats})
    assert mod.executor.num_compiled == 1
    d = TuningDecisions()
    for key in _recorded_keys(mod, params, feats):
        if key.startswith("gemm"):
            d.set_op(key, space.GemmVariant(tile_rows=8))
    mod.executor.set_decisions(d)
    mod.apply(params, {"feature": feats})
    assert mod.executor.num_compiled == 2      # new entry, not a stale hit
    mod.apply(params, {"feature": feats})
    assert mod.executor.num_compiled == 2      # stable under the new table


def test_tune_cache_schema_and_atomicity(tmp_path):
    path = str(tmp_path / "c.json")
    c = TuneCache(path)
    c.put("k1", {"kind": "gemm", "backend": "default", "tile_rows": 8,
                 "tile_n": None, "fuse_gather": None})
    c.save()
    c2 = TuneCache(path)
    assert space.variant_from_json(c2.get("k1")) == \
        space.GemmVariant(tile_rows=8)
    # incompatible schema versions are ignored, not misread
    with open(path, "w") as f:
        f.write('{"version": 999, "entries": {"k1": 1}}')
    assert TuneCache(path).get("k1") is None
    # corrupt files are ignored
    with open(path, "w") as f:
        f.write("not json")
    assert TuneCache(path).get("k1") is None


def test_tune_cache_invalidated_by_kernel_code_change(tmp_path):
    """Decisions measured against different kernel/codegen sources must not
    replay (warm caches never re-measure, so staleness would be forever)."""
    import json
    from repro.tune.cache import code_fingerprint
    path = str(tmp_path / "c.json")
    c = TuneCache(path)
    c.put("k1", {"kind": "trav", "backend": "default", "fuse_gather": False})
    c.save()
    with open(path) as f:
        payload = json.load(f)
    assert payload["code"] == code_fingerprint()
    payload["code"] = "0" * 12              # cache from "other" kernel code
    with open(path, "w") as f:
        json.dump(payload, f)
    assert TuneCache(path).get("k1") is None
