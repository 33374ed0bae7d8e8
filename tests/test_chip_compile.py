"""Compile every main-path Pallas kernel for a TPU v5e without the chip.

The TPU compiler ships with libtpu and compiles for a topology that is
described, not attached (``jax.experimental.topologies``). What it refuses
here — block shapes off the (8, 128) tiling, vector loads from SMEM,
scalar-prefetched maps larger than SMEM — it would refuse on the chip,
where interpret mode never looks. Nothing runs, so results are checked by
``chip_smoke.py`` on the chip and by the interpret-mode tests.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and every test worker imports this file.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import sampling_ops as SO
from repro.kernels import segment_mm as SK
from repro.kernels import traversal as TK
from repro.tune import device as D

D_FEAT, TILE, NB = 64, 128, 128      # the paper's width, v5e-sized tiles
TILES, NODE_BLOCKS, SRC_ROWS, GROUPS = 64, 16, 1000, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache here; keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, I32 = jnp.float32, jnp.int32
ROWS = TILES * TILE
_TRAV = dict(node_block=NB, num_node_blocks=NODE_BLOCKS)
_STAT = ((NODE_BLOCKS * NB, 1), F32)
KERNELS = {
    "segment_mm_padded": (
        lambda x, w, t: SK.segment_mm_padded(x, w, t, tile_rows=TILE),
        [((ROWS, D_FEAT), F32), ((GROUPS, D_FEAT, D_FEAT), F32),
         ((TILES,), I32)]),
    "segment_mm_gather_padded": (
        lambda x, w, g, t, s: SK.segment_mm_gather_padded(
            x, w, g, t, s, tile_rows=TILE),
        [((SRC_ROWS, D_FEAT), F32), ((GROUPS, D_FEAT, D_FEAT), F32),
         ((ROWS,), I32), ((TILES,), I32), ((ROWS, 1), F32)]),
    "segment_outer_padded": (
        lambda x, dy, t: SK.segment_outer_padded(
            x, dy, t, num_groups=GROUPS, tile_rows=TILE),
        [((ROWS, D_FEAT), F32), ((ROWS, D_FEAT), F32), ((TILES,), I32)]),
    "seg_stats_padded": (
        lambda s, ld, t: TK.seg_stats_padded(s, ld, t, **_TRAV),
        [((TILES, TILE), F32), ((TILES, TILE), I32), ((TILES,), I32)]),
    "seg_softmax_agg_padded": (
        lambda s, m, ld, t, mx, den: TK.seg_softmax_agg_padded(
            s, m, ld, t, mx, den, **_TRAV),
        [((TILES, TILE), F32), ((ROWS, D_FEAT), F32), ((TILES, TILE), I32),
         ((TILES,), I32), _STAT, _STAT]),
    "seg_softmax_agg_gather_padded": (
        lambda s, m, mm, ld, t, mx, den: TK.seg_softmax_agg_gather_padded(
            s, m, mm, ld, t, mx, den, **_TRAV),
        [((TILES, TILE), F32), ((SRC_ROWS, D_FEAT), F32), ((ROWS,), I32),
         ((TILES, TILE), I32), ((TILES,), I32), _STAT, _STAT]),
    "seg_weighted_agg_padded": (
        lambda s, m, ld, t: TK.seg_weighted_agg_padded(s, m, ld, t, **_TRAV),
        [((TILES, TILE), F32), ((ROWS, D_FEAT), F32), ((TILES, TILE), I32),
         ((TILES,), I32)]),
    "seg_weighted_agg_gather_padded": (
        lambda s, m, mm, ld, t: TK.seg_weighted_agg_gather_padded(
            s, m, mm, ld, t, **_TRAV),
        [((TILES, TILE), F32), ((SRC_ROWS, D_FEAT), F32), ((ROWS,), I32),
         ((TILES, TILE), I32), ((TILES,), I32)]),
    "seg_row_sum_padded": (
        lambda g, lr, t: TK.seg_row_sum_padded(
            g, lr, t, row_block=4 * NB, num_row_blocks=NODE_BLOCKS // 4),
        [((11, ROWS), F32), ((TILES, TILE), I32), ((TILES,), I32)]),
    "candidate_keys": (
        lambda s, c: SO.candidate_keys(s, c, 12345, 16, "pallas"),
        [((64, 108), I32), ((64, 108), I32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


def test_segment_mm_backward_compiles_for_narrow_weights(one_chip):
    """A hoisted weight product ``[R, k, 1]`` (RGAT's attention vectors)
    has a one-column forward GEMM; its backward GEMM has k columns and
    must pick a column tile the TPU accepts, not the forward's one."""
    from repro.kernels import layout as L
    sizes = np.full(GROUPS, 3 * TILE)
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    lay = ops.padded_segments_dev(L.pad_segments(ptr, TILE))
    m = int(ptr[-1])

    def loss(x, w):
        return jnp.sum(ops.segment_mm(x, w, lay, backend="pallas") ** 2)

    _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
             ((m, D_FEAT), F32), ((GROUPS, D_FEAT, 1), F32))


@pytest.mark.parametrize("heads,d", [(1, 11), (1, 64), (8, 32)])
def test_message_gather_backward_compiles(one_chip, heads, d):
    """The compact message gather's backward (``ops.gather_msg_rows``) at
    the cells' narrow widths and RGAT's and HGT's head counts: the slot
    gather, the row-sum kernel and the transposes back to ``[Em, H*d]``."""
    rng = np.random.default_rng(0)
    em, e = 3000, 5000
    rows = rng.integers(0, em, e).astype(np.int32)
    rev = ops.msg_rev_dev(rows, em, TILE)
    idx = jnp.asarray(rows)

    def loss(msg):
        return jnp.sum(ops.gather_msg_rows(msg, idx, rev, "pallas",
                                           heads) ** 2)

    text = _compile(jax.grad(loss), one_chip,
                    ((em, heads * d), F32)).as_text()
    assert "seg_row_sum_padded" in text


def test_fusion_gate_limits_compile(one_chip):
    """The largest source block and slot map the gather-fusion gate admits
    (on the v5e the CPU models) both compile: the gate never hands the
    compiler a fused kernel it refuses."""
    rows = D.fused_gather_budget_bytes() // (2 * 128 * 4)
    assert D.fused_gather_fits(rows, D_FEAT, 4, 0, TILE)
    assert not D.fused_gather_fits(rows + 8, D_FEAT, 4, 0, TILE)
    slots = TILE
    while D.fused_gather_fits(8, D_FEAT, 4, slots + TILE, TILE):
        slots += TILE
    tiles = slots // TILE
    fn = KERNELS["seg_weighted_agg_gather_padded"][0]
    _compile(fn, one_chip, ((tiles, TILE), F32), ((rows, D_FEAT), F32),
             ((slots,), I32), ((tiles, TILE), I32), ((tiles,), I32))


def test_train_step_instructions_have_owners_for_v5e(one_chip):
    """A small full-graph RGAT training step compiled for the v5e: every
    fusion and custom call has an owner in the model (``obs/device_ops``),
    and every Pallas call is named after its wrapper, as the roofline
    readers' ``kernel_names.json`` expects."""
    import json
    import pathlib
    from repro.core.graph import synthetic_heterograph
    from repro.obs import device_ops
    from repro.optim import AdamW
    from repro.train import EngineConfig, FullGraphTrainer, RGNNEngine
    graph = synthetic_heterograph(num_nodes=400, num_edges=1200,
                                  num_ntypes=3, num_etypes=12, seed=0)
    eng = RGNNEngine(graph, EngineConfig(
        model="rgat", layers=2, dim=D_FEAT, hidden=D_FEAT, classes=11,
        backend="pallas", tile=TILE, node_block=NB, seed=0))
    feats = jnp.zeros((graph.num_nodes, D_FEAT), F32)
    tr = FullGraphTrainer(eng, feats, np.zeros(graph.num_nodes, np.int32),
                          np.arange(graph.num_nodes), opt=AdamW(), log=None)
    state = tr.init_state(eng.init_params(jax.random.key(0)))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=one_chip),
        (state, eng.gt, eng.layouts, tr._idx, tr._labels_train,
         {"feature": tr.feats}))
    text = jax.jit(tr.step_exec.hector_train_step).lower(
        *args).compile().as_text()
    module, table = device_ops.parse_hlo(text)
    assert module == "jit_hector_train_step"
    unowned_ok = set()      # none at this size
    kernels = [p for ps in json.loads(
        (pathlib.Path(__file__).parents[1] / "bench" / "metrics" /
         "kernel_names.json").read_text()).values() for p in ps]
    seen = 0
    for line in text.splitlines():
        name = line.split("=")[0].split()[-1].lstrip("%") if "=" in line \
            else None
        if name not in table or not (" fusion(" in line
                                     or " custom-call(" in line):
            continue
        seen += 1
        assert table[name] is not None or name in unowned_ok, line[:200]
        if "tpu_custom_call" in line:
            assert any(name.startswith(k + ".") or name == k
                       for k in kernels), name
    assert seen > 50
