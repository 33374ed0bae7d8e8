"""Per-kernel shape/dtype sweeps + gradient checks vs the ref.py oracles
(deliverable c: each Pallas kernel validated in interpret mode)."""
import collections
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import layout as L, ops, ref as R

BACKENDS = ["xla", "pallas_interpret"]


def _segments(rng, n_groups, max_size):
    sizes = rng.integers(0, max_size, n_groups)
    ptr = np.zeros(n_groups + 1, np.int64)
    np.cumsum(sizes, out=ptr[1:])
    seg_ids = np.repeat(np.arange(n_groups), sizes)
    return ptr, seg_ids, int(sizes.sum())


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("k,n,tile", [(8, 8, 8), (16, 24, 8), (32, 128, 16),
                                      (64, 48, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_mm_sweep(rng, backend, k, n, tile, dtype):
    ptr, seg_ids, m = _segments(rng, n_groups=5, max_size=21)
    if m == 0:
        pytest.skip("empty")
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(5, k, n)), dtype)
    lay = ops.padded_segments_dev(L.pad_segments(ptr, tile))
    y = ops.segment_mm(x, w, lay, backend=backend)
    y_ref = R.segment_mm_ref(x, w, jnp.asarray(seg_ids))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_mm_row_scale_fusion(rng, backend):
    ptr, seg_ids, m = _segments(rng, 4, 17)
    x = jnp.asarray(rng.normal(size=(m, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 12, 20)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    lay = ops.padded_segments_dev(L.pad_segments(ptr, 8))
    y = ops.segment_mm(x, w, lay, row_scale=scale, backend=backend)
    y_ref = R.segment_mm_ref(x, w, jnp.asarray(seg_ids), scale)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_mm_grads(rng, backend):
    ptr, seg_ids, m = _segments(rng, 5, 13)
    x = jnp.asarray(rng.normal(size=(m, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 16, 24)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    lay = ops.padded_segments_dev(L.pad_segments(ptr, 8))

    def f(x, w, s):
        return jnp.sum(jnp.sin(ops.segment_mm(x, w, lay, row_scale=s,
                                              backend=backend)))

    def f_ref(x, w, s):
        return jnp.sum(jnp.sin(R.segment_mm_ref(x, w, jnp.asarray(seg_ids), s)))

    g = jax.grad(f, argnums=(0, 1, 2))(x, w, s)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, s)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _dst_layout(rng, n_nodes, n_edges, tile=8, nb=8):
    dst = np.sort(rng.integers(0, n_nodes, n_edges)).astype(np.int32)
    canon = rng.permutation(dst)
    perm = np.argsort(canon, kind="stable").astype(np.int32)
    ptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(np.bincount(canon[perm], minlength=n_nodes), out=ptr[1:])
    bc = ops.blocked_csr_dev(L.block_csr(ptr, tile, nb), perm)
    return jnp.asarray(canon), bc


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_nodes,n_edges,d", [(13, 60, 4), (40, 200, 12),
                                               (7, 7, 16)])
def test_softmax_agg_sweep(rng, backend, n_nodes, n_edges, d):
    dst, bc = _dst_layout(rng, n_nodes, n_edges)
    scores = jnp.asarray(rng.normal(size=(n_edges,)), jnp.float32)
    msg = jnp.asarray(rng.normal(size=(n_edges, d)), jnp.float32)
    out = ops.edge_softmax_agg(scores, msg, dst, n_nodes, bc=bc,
                               backend=backend)
    ref = R.softmax_agg_ref(scores, msg, dst, n_nodes)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_softmax_agg_grads(rng, backend):
    dst, bc = _dst_layout(rng, 11, 80)
    scores = jnp.asarray(rng.normal(size=(80,)), jnp.float32)
    msg = jnp.asarray(rng.normal(size=(80, 6)), jnp.float32)

    def f(s, m):
        return jnp.sum(jnp.cos(
            ops.edge_softmax_agg(s, m, dst, 11, bc=bc, backend=backend)))

    def f_ref(s, m):
        return jnp.sum(jnp.cos(R.softmax_agg_ref(s, m, dst, 11)))

    g = jax.grad(f, argnums=(0, 1))(scores, msg)
    gr = jax.grad(f_ref, argnums=(0, 1))(scores, msg)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_weighted_agg(rng, backend):
    dst, bc = _dst_layout(rng, 9, 50)
    scale = jnp.asarray(rng.normal(size=(50,)), jnp.float32)
    msg = jnp.asarray(rng.normal(size=(50, 5)), jnp.float32)
    out = ops.weighted_agg(scale, msg, dst, 9, bc=bc, backend=backend)
    ref = R.weighted_agg_ref(scale, msg, dst, 9)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# in-kernel gather access schemes (gather-fused variants)
# ---------------------------------------------------------------------------
def _gather_setup(rng, n_src=23, n_groups=4, max_size=17, k=6, n=5, tile=8):
    ptr, seg_ids, m = _segments(rng, n_groups, max_size)
    feats = jnp.asarray(rng.normal(size=(n_src, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n_groups, k, n)), jnp.float32)
    idx = rng.integers(0, n_src, size=m).astype(np.int32)
    ps = L.pad_segments(ptr, tile)
    lay = ops.padded_segments_dev(ps)
    gmap = jnp.asarray(L.compose_gather_rows(ps, idx))
    return feats, w, idx, seg_ids, lay, gmap, m


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("with_scale", [False, True])
def test_segment_mm_gather_matches_materialized(rng, backend, with_scale):
    feats, w, idx, seg_ids, lay, gmap, m = _gather_setup(rng)
    scale = (jnp.asarray(rng.normal(size=(m,)), jnp.float32)
             if with_scale else None)
    fused = ops.segment_mm_gather(feats, w, lay, gmap, row_scale=scale,
                                  backend=backend)
    materialized = ops.segment_mm(feats[idx], w, lay, row_scale=scale,
                                  backend=backend)
    ref = R.gather_mm_ref(feats, w, jnp.asarray(idx), jnp.asarray(seg_ids),
                          scale)
    np.testing.assert_allclose(fused, materialized, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(fused, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_mm_gather_grads(rng, backend):
    feats, w, idx, seg_ids, lay, gmap, m = _gather_setup(rng)
    scale = jnp.asarray(rng.normal(size=(m,)), jnp.float32)

    def f(feats, w, s):
        return jnp.sum(jnp.sin(ops.segment_mm_gather(
            feats, w, lay, gmap, row_scale=s, backend=backend)))

    def f_ref(feats, w, s):
        return jnp.sum(jnp.sin(R.gather_mm_ref(
            feats, w, jnp.asarray(idx), jnp.asarray(seg_ids), s)))

    g = jax.grad(f, argnums=(0, 1, 2))(feats, w, scale)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(feats, w, scale)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _iter_eqns_outside_kernels(jaxpr):
    """All eqns reachable from ``jaxpr`` WITHOUT descending into Pallas
    kernel bodies — i.e. everything XLA would execute around the kernels."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue

        def _sub(v):
            if hasattr(v, "jaxpr") and hasattr(v, "eqns") is False:
                return [v.jaxpr]  # ClosedJaxpr
            if hasattr(v, "eqns"):
                return [v]        # Jaxpr
            if isinstance(v, (list, tuple)):
                return [j for item in v for j in _sub(item)]
            return []

        for v in eqn.params.values():
            for sub in _sub(v):
                yield from _iter_eqns_outside_kernels(sub)


def test_segment_mm_gather_no_prekernel_edge_copy(rng):
    """Acceptance: the gather-fused GEMM never materializes an edge-wide
    [rows, k] input copy outside the Pallas kernel (the gather lives in the
    kernel's index space). k=6 != n=5 disambiguates input-side gathers from
    the post-kernel output unpadding."""
    feats, w, idx, seg_ids, lay, gmap, m = _gather_setup(rng)
    k = feats.shape[1]
    rp = int(lay.row_map.shape[0])

    def fused(feats, w):
        return ops.segment_mm_gather(feats, w, lay, gmap,
                                     backend="pallas_interpret")

    jaxpr = jax.make_jaxpr(fused)(feats, w)
    gather_prims = {"gather", "take", "dynamic_slice"}
    banned = {(m, k), (rp, k)}   # edge-wide input copies
    offending = [
        eqn for eqn in _iter_eqns_outside_kernels(jaxpr.jaxpr)
        if eqn.primitive.name in gather_prims
        and any(tuple(o.aval.shape) in banned for o in eqn.outvars)
    ]
    assert not offending, (
        f"edge-wide input gather materialized outside the kernel: "
        f"{offending}")
    # the materialized path DOES produce one (sanity check of the detector)
    def materialized(feats, w):
        return ops.segment_mm(feats[jnp.asarray(idx)], w, lay,
                              backend="pallas_interpret")
    jaxpr_m = jax.make_jaxpr(materialized)(feats, w)
    hits = [
        eqn for eqn in _iter_eqns_outside_kernels(jaxpr_m.jaxpr)
        if eqn.primitive.name in gather_prims
        and any(tuple(o.aval.shape) in banned for o in eqn.outvars)
    ]
    assert hits, "detector failed to flag the materialized-gather baseline"


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("compact", [False, True])
def test_softmax_agg_gather_fused_matches_materialized(rng, backend, compact):
    n_nodes, n_edges, d = 13, 60, 4
    dst, bc = _dst_layout(rng, n_nodes, n_edges)
    scores = jnp.asarray(rng.normal(size=(n_edges,)), jnp.float32)
    if compact:
        n_rows = 20
        msg_rows = jnp.asarray(rng.integers(0, n_rows, n_edges), jnp.int32)
        msg = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
        msg_e = msg[msg_rows]
    else:
        msg_rows = None
        msg = jnp.asarray(rng.normal(size=(n_edges, d)), jnp.float32)
        msg_e = msg
    fused = ops.edge_softmax_agg(scores, msg, dst, n_nodes, bc=bc,
                                 backend=backend, msg_rows=msg_rows,
                                 fuse_gather=True)
    materialized = ops.edge_softmax_agg(scores, msg, dst, n_nodes, bc=bc,
                                        backend=backend, msg_rows=msg_rows,
                                        fuse_gather=False)
    ref = R.softmax_agg_ref(scores, msg_e, dst, n_nodes)
    np.testing.assert_allclose(fused, materialized, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_weighted_agg_gather_fused_compact_and_grads(rng, backend):
    n_nodes, n_edges, n_rows, d = 9, 50, 17, 5
    dst, bc = _dst_layout(rng, n_nodes, n_edges)
    msg_rows = jnp.asarray(rng.integers(0, n_rows, n_edges), jnp.int32)
    scale = jnp.asarray(rng.normal(size=(n_edges,)), jnp.float32)
    msg = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)

    def f(s, m):
        return jnp.sum(jnp.cos(ops.weighted_agg(
            s, m, dst, n_nodes, bc=bc, backend=backend,
            msg_rows=msg_rows, fuse_gather=True)))

    def f_ref(s, m):
        return jnp.sum(jnp.cos(R.weighted_agg_ref(s, m[msg_rows], dst,
                                                  n_nodes)))

    np.testing.assert_allclose(
        ops.weighted_agg(scale, msg, dst, n_nodes, bc=bc, backend=backend,
                         msg_rows=msg_rows),
        R.weighted_agg_ref(scale, msg[msg_rows], dst, n_nodes),
        rtol=1e-5, atol=1e-5)
    g = jax.grad(f, argnums=(0, 1))(scale, msg)
    gr = jax.grad(f_ref, argnums=(0, 1))(scale, msg)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    n_groups=st.integers(1, 6),
    k=st.sampled_from([4, 8, 12]),
    n=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 3),
)
def test_property_segment_mm_matches_ref(n_groups, k, n, seed):
    rng = np.random.default_rng(seed)
    ptr, seg_ids, m = _segments(rng, n_groups, 11)
    if m == 0:
        return
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n_groups, k, n)), jnp.float32)
    lay = ops.padded_segments_dev(L.pad_segments(ptr, 4))
    y = ops.segment_mm(x, w, lay, backend="pallas_interpret")
    np.testing.assert_allclose(
        y, R.segment_mm_ref(x, w, jnp.asarray(seg_ids)), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the tile padding permutation (``ops.pad_rows`` / ``ops.unpad_rows``)
# ---------------------------------------------------------------------------
_SIZES = np.array([5, 0, 13, 8, 0, 1, 17, 0])   # empty groups, first to last


def _padding(sizes=_SIZES, tile=8, grow_tiles=0):
    ptr = np.zeros(len(sizes) + 1, np.int64)
    np.cumsum(sizes, out=ptr[1:])
    ps = L.pad_segments(ptr, tile)
    if grow_tiles:
        ps = L.pad_segments_rows(ps, ps.padded_rows + grow_tiles * tile)
    return ps


def _plain_padding(mp):
    """Install the padding pair as plain gathers, whose transposes XLA
    derives as scatter-adds into zeros (the formulation the custom VJPs
    replace)."""
    def pad_rows(x, lay):
        valid = lay.row_map >= 0
        xp = x[jnp.maximum(lay.row_map, 0)]
        return jnp.where(valid if x.ndim == 1 else valid[:, None], xp, 0.0)

    mp.setattr(ops, "pad_rows", pad_rows)
    mp.setattr(ops, "unpad_rows", lambda y_p, lay: y_p[lay.inv_map])


def _gemm_case(rng, op, ps, with_scale, k=12, n=10, n_src=30):
    """``(loss(x, w, s, lay, backend), x, w, s, lay)`` of ``op`` over the
    layout ``ps``."""
    lay = ops.padded_segments_dev(ps)
    m = int(ps.seg_sizes.sum())
    idx = rng.integers(0, n_src, m).astype(np.int32)
    feats = jnp.asarray(rng.normal(size=(n_src, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(ps.num_groups, k, n)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    gmap = jnp.asarray(L.compose_gather_rows(ps, idx))

    def loss(x, w, s, lay, backend):
        scale = s if with_scale else None
        if op == "segment_mm":
            y = ops.segment_mm(x, w, lay, row_scale=scale, backend=backend)
        else:
            y = ops.segment_mm_gather(x, w, lay, gmap, row_scale=scale,
                                      backend=backend)
        return jnp.sum(y * c)

    x = feats[idx] if op == "segment_mm" else feats
    return loss, x, w, s, lay


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", ["segment_mm", "segment_mm_gather"])
@pytest.mark.parametrize("with_scale", [False, True])
@pytest.mark.parametrize("grow_tiles", [0, 3])
def test_padding_transpose_grads_bitwise(rng, monkeypatch, backend, op,
                                         with_scale, grow_tiles):
    """Gradients through the custom-VJP padding pair are bitwise equal to
    those through the scatter-add transpose XLA derives for the plain
    gathers, with empty groups and with a layout grown by
    ``pad_segments_rows``."""
    loss, x, w, s, lay = _gemm_case(rng, op, _padding(grow_tiles=grow_tiles),
                                    with_scale)

    def grads():
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)),
                       static_argnums=4)(x, w, s, lay, backend)

    with monkeypatch.context() as mp:
        _plain_padding(mp)
        want = grads()
    got = grads()
    for name, a, b in zip("xws", got, want):
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=name)


_INSTR = re.compile(r"=\s*(\w+)\[([\d,]*)\]\S*\s+([a-z][\w\-]*)\(")


def _compiled_ops(fn, *args):
    """(opcode, result shape) of every instruction of ``fn``'s optimized
    HLO on the CPU."""
    text = jax.jit(lambda *a: fn(*a), static_argnums=len(args) - 1) \
        .lower(*args).compile().as_text()     # a fresh trace every call
    return [(m.group(3), tuple(int(d) for d in m.group(2).split(",") if d))
            for m in _INSTR.finditer(text)]


@pytest.mark.parametrize("op", ["segment_mm", "segment_mm_gather"])
def test_padding_transpose_hlo(rng, monkeypatch, op):
    """The XLA backend's compiled grad holds no scatter into Rp or M rows,
    and the forward compiles to the opcodes of the plain gathers."""
    ps = _padding()
    rows = {ps.padded_rows, int(ps.seg_sizes.sum())}
    loss, x, w, s, lay = _gemm_case(rng, op, ps, with_scale=True)
    # dW's scatter (into R rows) and the source gather's (into the 30
    # source rows of ``segment_mm_gather``) stay; neither may alias
    assert not rows & {30, ps.num_groups}

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def padded_scatters(ops_):
        return [shape for opcode, shape in ops_
                if opcode == "scatter" and shape and shape[0] in rows]

    with monkeypatch.context() as mp:
        _plain_padding(mp)
        plain_grad = _compiled_ops(grad, x, w, s, lay, "xla")
        plain_fwd = _compiled_ops(loss, x, w, s, lay, "xla")
    assert padded_scatters(plain_grad)        # the detector sees them
    assert not padded_scatters(_compiled_ops(grad, x, w, s, lay, "xla"))
    assert collections.Counter(o for o, _ in _compiled_ops(
        loss, x, w, s, lay, "xla")) == collections.Counter(
            o for o, _ in plain_fwd)


@pytest.mark.parametrize("builder", ["pad_segments", "pad_segments_rows",
                                     "device_pad_segments"])
@pytest.mark.parametrize("sizes", [_SIZES, np.array([0]), np.array([3, 9])])
def test_padding_map_invariant(builder, sizes):
    """``inv_map`` is injective, ``row_map[inv_map] == arange(M)``, and
    every other slot of ``row_map`` is -1: what the padding pair's VJPs
    rest on."""
    tile, m = 4, int(sizes.sum())
    if builder == "device_pad_segments":
        ptr = np.zeros(len(sizes) + 1, np.int32)
        np.cumsum(sizes, out=ptr[1:])
        rp = tile * (-(-m // tile) + len(sizes) + 2)   # room for any padding
        row_map, inv_map, _ = L.device_pad_segments(
            jnp.asarray(ptr), jnp.asarray(np.repeat(np.arange(len(sizes)),
                                                    sizes).astype(np.int32)),
            tile, rp)
        row_map, inv_map = np.asarray(row_map), np.asarray(inv_map)
    else:
        grow = 2 if builder == "pad_segments_rows" else 0
        ps = _padding(sizes, tile, grow_tiles=grow)
        row_map, inv_map = ps.row_map, ps.inv_map
    assert inv_map.shape == (m,)
    assert len(np.unique(inv_map)) == m
    np.testing.assert_array_equal(row_map[inv_map], np.arange(m))
    others = np.ones(row_map.shape[0], bool)
    others[inv_map] = False
    assert np.all(row_map[others] == -1)


def test_padded_perm_traced_counter(rng):
    """One count per padding or un-padding traced, none per cached call."""
    from repro import obs
    ps = _padding()
    _, x, w, s, lay = _gemm_case(rng, "segment_mm", ps, with_scale=True)
    f = jax.jit(lambda x, w, s, lay: ops.segment_mm(x, w, lay, row_scale=s))
    with obs.scope() as st:
        f(x, w, s, lay)
        f(x, w, s, lay)
        reg = st.registry
        assert reg.counter("padded_perm_traced", op="pad").value == 2
        assert reg.counter("padded_perm_traced", op="unpad").value == 1
        jax.jit(lambda x, lay: ops.segment_mm(x, w, lay))(x, lay)
        assert reg.counter_total("padded_perm_traced") == 5


# ---------------------------------------------------------------------------
# attention heads: per-head GEMM, edge softmax and aggregation
# (``ops`` folds H heads into rows: head-major GEMM rows, and one graph of
# H * Np destinations for the traversal kernels)
def _same_bits(a, b):
    np.testing.assert_array_equal(_bits(a), _bits(b))


def _heads_gemm_case(rng, heads, k=4, n=3):
    ptr, seg_ids, m = _segments(rng, 5, 13)
    x = jnp.asarray(rng.normal(size=(m, heads * k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, heads, k, n)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    lay = ops.padded_segments_dev(L.pad_segments(ptr, 8))
    return x, w, s, lay, jnp.asarray(seg_ids)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("with_scale", [False, True])
def test_segment_mm_heads_matches_ref(rng, backend, heads, with_scale):
    """Each row's h-th block times its type's matrix of head h, forward and
    VJP, against ``ref.segment_mm_heads_ref``. Tolerances as the one-head
    GEMM tests': float32 sums in another order."""
    x, w, s, lay, seg = _heads_gemm_case(rng, heads)
    s = s if with_scale else None

    def f(x, w):
        return ops.segment_mm(x, w, lay, row_scale=s, backend=backend)

    def f_ref(x, w):
        y = R.segment_mm_heads_ref(x, w, seg)
        return y if s is None else y * s[:, None]

    np.testing.assert_allclose(f(x, w), f_ref(x, w), rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1))(x, w)
    gr = jax.grad(lambda *a: jnp.sum(jnp.sin(f_ref(*a))),
                  argnums=(0, 1))(x, w)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_mm_one_head_is_todays_kernel(rng, backend):
    """One head through the per-head path equals the one-head GEMM bit for
    bit, forward and VJP."""
    x, w, s, lay, _ = _heads_gemm_case(rng, 1, k=6, n=5)

    def f(x, w):
        return jnp.sum(jnp.sin(ops.segment_mm(x, w, lay, row_scale=s,
                                              backend=backend)))

    w3 = w[:, 0]
    _same_bits(ops.segment_mm(x, w, lay, row_scale=s, backend=backend),
               ops.segment_mm(x, w3, lay, row_scale=s, backend=backend))
    gx4, gw4 = jax.grad(f, argnums=(0, 1))(x, w)
    gx3, gw3 = jax.grad(f, argnums=(0, 1))(x, w3)
    _same_bits(gx4, gx3)
    _same_bits(gw4[:, 0], gw3)


def _heads_traversal_case(rng, heads, compact, n_nodes=13, n_edges=70, d=3):
    dst, bc = _dst_layout(rng, n_nodes, n_edges)
    scores = jnp.asarray(rng.normal(size=(n_edges, heads)), jnp.float32)
    n_rows = 20 if compact else n_edges
    msg = jnp.asarray(rng.normal(size=(n_rows, heads * d)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, n_rows, n_edges), jnp.int32) \
        if compact else None
    return dst, bc, scores, msg, rows


def _per_head_ref(agg, scores, msg, rows, dst, n_nodes):
    msg_e = msg if rows is None else msg[rows]
    out = agg(scores, msg_e.reshape(msg_e.shape[0], scores.shape[1], -1),
              dst, n_nodes)
    return out.reshape(n_nodes, -1)


_TRAVERSALS = {"softmax_agg": (ops.edge_softmax_agg, R.softmax_agg_ref),
               "weighted_agg": (ops.weighted_agg, R.weighted_agg_ref)}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", sorted(_TRAVERSALS))
@pytest.mark.parametrize("compact", [False, True])
@pytest.mark.parametrize("fuse", [False, True])
def test_traversal_heads_matches_ref(rng, backend, op, compact, fuse):
    """H = 3 score columns, each weighing its block of message columns,
    forward and VJP against ``kernels/ref.py`` (one softmax per column);
    vanilla and compact messages, gather fused or not. Tolerances as the
    one-head traversal tests': float32 sums in another order."""
    fn, ref = _TRAVERSALS[op]
    dst, bc, scores, msg, rows = _heads_traversal_case(rng, 3, compact)

    def f(s, m):
        return fn(s, m, dst, 13, bc=bc, backend=backend, msg_rows=rows,
                  fuse_gather=fuse)

    def f_ref(s, m):
        return _per_head_ref(ref, s, m, rows, dst, 13)

    np.testing.assert_allclose(f(scores, msg), f_ref(scores, msg),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda *a: jnp.sum(jnp.cos(f(*a))), argnums=(0, 1))(
        scores, msg)
    gr = jax.grad(lambda *a: jnp.sum(jnp.cos(f_ref(*a))), argnums=(0, 1))(
        scores, msg)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", sorted(_TRAVERSALS))
@pytest.mark.parametrize("compact", [False, True])
def test_traversal_one_head_is_todays_kernel(rng, backend, op, compact):
    """One score column through the per-head path (heads folded into the
    node axis) equals the one-head traversal bit for bit, forward and
    VJP."""
    fn, _ = _TRAVERSALS[op]
    dst, bc, scores, msg, rows = _heads_traversal_case(rng, 1, compact)

    def f(s, m):
        return fn(s, m, dst, 13, bc=bc, backend=backend, msg_rows=rows)

    _same_bits(f(scores, msg), f(scores[:, 0], msg))
    loss = lambda *a: jnp.sum(jnp.cos(f(*a)))  # noqa: E731
    gs2, gm2 = jax.grad(loss, argnums=(0, 1))(scores, msg)
    gs1, gm1 = jax.grad(loss, argnums=(0, 1))(scores[:, 0], msg)
    _same_bits(gs2[:, 0], gs1)
    _same_bits(gm2, gm1)


def test_edge_softmax_heads_is_per_column(rng):
    dst, _, scores, _, _ = _heads_traversal_case(rng, 4, False)
    att = ops.edge_softmax(scores, dst, 13)
    for h in range(4):
        np.testing.assert_array_equal(
            att[:, h], R.edge_softmax_ref(scores[:, h], dst, 13))


def test_heads_traced_counter(rng):
    """One count per per-head op traced, labelled with its head count."""
    from repro import obs
    x, w, _, lay, _ = _heads_gemm_case(rng, 2)
    dst, bc, scores, msg, _ = _heads_traversal_case(rng, 3, False)
    with obs.scope() as st:
        f = jax.jit(lambda x, w: ops.segment_mm(x, w, lay,
                                                backend="pallas_interpret"))
        f(x, w)
        f(x, w)
        ops.edge_softmax_agg(scores, msg, dst, 13, bc=bc,
                             backend="pallas_interpret")
        ops.edge_softmax_agg(scores[:, 0], msg[:, :3], dst, 13, bc=bc,
                             backend="pallas_interpret")
        reg = st.registry
        assert reg.counter("heads_traced", op="gemm", heads="2").value == 1
        assert reg.counter("heads_traced", op="softmax_agg",
                           heads="3").value == 1
        assert reg.counter_total("heads_traced") == 2


# ---------------------------------------------------------------------------
# the compact message gather ``ops.gather_msg_rows`` and its sum by message
# row over the message-row layout (``layout.msg_rows_csr``)
def _msg_rows_case(rng, rows, d, n_edges=90, n_rows=40):
    """(msg [n_rows, d], msg_rows [E], layout) with every row read by 1-4
    edges (``repeated``), some rows read by none (``unread``), or no edges
    (``none``)."""
    if rows == "none":
        msg_rows = np.zeros(0, np.int32)
    elif rows == "unread":
        msg_rows = rng.integers(0, n_rows // 2, n_edges) * 2
    else:
        msg_rows = np.concatenate([np.arange(n_rows),
                                   rng.integers(0, n_rows, n_edges - n_rows)])
        msg_rows = rng.permutation(msg_rows)
    msg_rows = msg_rows.astype(np.int32)
    msg = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
    return msg, msg_rows, ops.msg_rev_dev(msg_rows, n_rows, edge_tile=8)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("d", [6, 11, 32, 64])
@pytest.mark.parametrize("rows", ["repeated", "unread", "none"])
def test_gather_msg_rows_matches_plain_gather(rng, backend, d, rows):
    """The forward is ``msg[msg_rows]`` bit for bit; the gradient equals the
    scatter-add XLA derives for the plain gather up to float32 rounding
    (each row's sum runs in another order)."""
    msg, msg_rows, rev = _msg_rows_case(rng, rows, d)
    idx = jnp.asarray(msg_rows)
    c = jnp.asarray(rng.normal(size=(len(msg_rows), d)), jnp.float32)
    _same_bits(ops.gather_msg_rows(msg, idx, rev, backend), msg[idx])

    def loss(gather):
        return lambda m: jnp.sum(jnp.sin(gather(m)) * c)

    got = jax.grad(loss(lambda m: ops.gather_msg_rows(m, idx, rev,
                                                      backend)))(msg)
    want = jax.grad(loss(lambda m: m[idx]))(msg)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    if rows == "unread":
        assert not np.any(np.asarray(got)[1::2])


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", sorted(_TRAVERSALS))
@pytest.mark.parametrize("heads", [1, 8])
def test_traversal_msg_rev_matches_ref(rng, backend, op, heads):
    """Unfused aggregations of compact messages with the message-row layout
    (one head, and 8 heads whose gather runs on whole rows), forward and VJP
    against ``kernels/ref.py``, and against the same op whose message
    gradient XLA scatters."""
    fn, ref = _TRAVERSALS[op]
    dst, bc, scores, msg, rows = _heads_traversal_case(rng, heads, True)
    rev = ops.msg_rev_dev(np.asarray(rows), msg.shape[0], edge_tile=8)
    scores = scores if heads > 1 else scores[:, 0]

    def f(rev):
        return lambda s, m: fn(s, m, dst, 13, bc=bc, backend=backend,
                               msg_rows=rows, fuse_gather=False, msg_rev=rev)

    def f_ref(s, m):
        s = s if heads > 1 else s[:, None]
        return _per_head_ref(ref, s, m, rows, dst, 13)

    np.testing.assert_allclose(f(rev)(scores, msg), f_ref(scores, msg),
                               rtol=1e-5, atol=1e-5)

    def grads(fn_):
        return jax.grad(lambda *a: jnp.sum(jnp.cos(fn_(*a))),
                        argnums=(0, 1))(scores, msg)

    got = grads(f(rev))
    for want, tol in ((grads(f_ref), 1e-4), (grads(f(None)), 1e-5)):
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def test_msg_row_sum_traced_counter(rng):
    """One count per unfused compact message gather traced, by op and by
    the backward it gets; none for a gather-fused or vanilla message."""
    from repro import obs
    dst, bc, scores, msg, rows = _heads_traversal_case(rng, 1, True)
    rev = ops.msg_rev_dev(np.asarray(rows), msg.shape[0], edge_tile=8)
    kw = dict(bc=bc, backend="pallas_interpret", msg_rows=rows)
    with obs.scope() as st:
        f = jax.jit(lambda s, m: ops.edge_softmax_agg(
            s, m, dst, 13, fuse_gather=False, msg_rev=rev, **kw))
        f(scores[:, 0], msg)
        f(scores[:, 0], msg)
        ops.weighted_agg(scores[:, 0], msg, dst, 13, fuse_gather=False, **kw)
        ops.weighted_agg(scores[:, 0], msg, dst, 13, fuse_gather=True, **kw)
        reg = st.registry
        assert reg.counter("msg_row_sum_traced", op="softmax_agg",
                           path="kernel").value == 1
        assert reg.counter("msg_row_sum_traced", op="weighted_agg",
                           path="scatter").value == 1
        assert reg.counter_total("msg_row_sum_traced") == 2


@pytest.mark.parametrize("graph", ["am", "bgs", "unread", "none"])
def test_msg_rows_layout_invariant(graph):
    """Every edge sits in one slot of the message-row layout, slots run
    through rows in order within each row's block, pads are -1, and on the
    am- and bgs-shaped graphs the padded slots stay within 1.15 edges."""
    from repro.core.graph import CPU_REDUCED_SCALES, table3_graph
    tile = 128
    if graph in ("am", "bgs"):
        hg = table3_graph(graph, scale=CPU_REDUCED_SCALES[graph])
        rows, n_rows = hg.edge_to_unique, hg.num_unique
    else:
        n_rows = 300
        rows = (np.arange(0, n_rows, 3).repeat(2) if graph == "unread"
                else np.zeros(0)).astype(np.int32)
    bc, perm = L.msg_rows_csr(rows, n_rows, tile)
    lay = ops.blocked_csr_dev(bc, perm)
    edge_map = np.asarray(lay.edge_map)
    e = len(rows)
    assert np.array_equal(np.sort(edge_map[edge_map >= 0]), np.arange(e))
    pads = edge_map < 0
    assert np.all(bc.local_dst[pads] == bc.node_block)
    row = (np.repeat(bc.tile_to_block, tile) * bc.node_block
           + bc.local_dst)[~pads]
    assert np.array_equal(row, rows[edge_map[~pads]])
    assert np.all(np.diff(row) >= 0)
    # every row block owns a tile, so every output block is zeroed
    assert set(bc.tile_to_block) == set(range(bc.num_node_blocks))
    if graph in ("am", "bgs"):
        assert bc.padded_edges <= 1.15 * e, bc.padded_edges / e
