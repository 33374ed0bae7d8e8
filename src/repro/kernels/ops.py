"""jit'd wrapper ops around the Pallas templates + XLA fallbacks.

These are the operators the Hector code generator instantiates. Every op has
three interchangeable execution paths selected by ``backend``:

  'xla'               tile-aligned einsum formulation (natively differentiable,
                      GSPMD-shardable; used on CPU and in the multi-pod dry-run)
  'pallas'            the TPU kernel (custom_vjp; backward = template-derived
                      outer-product GEMM + traversal instances, paper §3.5)
  'pallas_interpret'  same kernel body executed in interpret mode (CPU tests)

Numerical contract: all paths match ``kernels/ref.py`` oracles.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro import compat, obs
from repro.kernels import layout as L
from repro.kernels import ref as R
from repro.kernels import segment_mm as SK
from repro.kernels import traversal as TK

Backend = str  # 'xla' | 'pallas' | 'pallas_interpret'


# ---------------------------------------------------------------------------
# device-side layout bundles (pytrees: arrays are leaves, shape metadata is
# static aux data, so whole layouts can flow through jit as arguments and
# still parameterize the kernel factories with plain Python ints)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class PaddedSegmentsDev:
    """A tile-aligned padded layout on the device.

    Invariant (``pad_segments``, ``pad_segments_rows`` and
    ``device_pad_segments`` all build it so): ``inv_map`` is injective,
    ``row_map[inv_map] == arange(M)``, and every other slot of ``row_map``
    is -1. ``pad_rows`` and ``unpad_rows`` rely on it to transpose each
    other exactly.
    """

    row_map: jnp.ndarray      # [Rp] compact row of each slot, -1 for pad
    inv_map: jnp.ndarray      # [M] slot of each compact row
    t2g: jnp.ndarray          # [T]
    tile: int
    num_groups: int


jtu.register_pytree_node(
    PaddedSegmentsDev,
    lambda p: ((p.row_map, p.inv_map, p.t2g), (p.tile, p.num_groups)),
    lambda aux, ch: PaddedSegmentsDev(*ch, *aux),
)


@dataclasses.dataclass(frozen=True, eq=False)
class BlockedCSRDev:
    """A blocked CSR layout on the device: edges sorted by destination
    (``blocked``), or by the message row they read (``msg_rev``, whose
    "nodes" are the compact message rows)."""

    edge_map: jnp.ndarray         # [Ep] canonical edge index or -1
    edge_map_unique: jnp.ndarray  # [Ep] compact (unique-pair) row or -1
    local_dst: jnp.ndarray        # [T, tile]
    t2b: jnp.ndarray              # [T]
    edge_tile: int
    node_block: int
    num_node_blocks: int
    num_nodes: int


jtu.register_pytree_node(
    BlockedCSRDev,
    lambda b: ((b.edge_map, b.edge_map_unique, b.local_dst, b.t2b),
               (b.edge_tile, b.node_block, b.num_node_blocks, b.num_nodes)),
    lambda aux, ch: BlockedCSRDev(*ch, *aux),
)


def padded_segments_dev(ps: L.PaddedSegments) -> PaddedSegmentsDev:
    return PaddedSegmentsDev(
        row_map=jnp.asarray(ps.row_map),
        inv_map=jnp.asarray(ps.inv_map),
        t2g=jnp.asarray(ps.tile_to_group),
        tile=ps.tile,
        num_groups=ps.num_groups,
    )


def blocked_csr_dev(
    bc: L.BlockedCSR, perm_dst: np.ndarray,
    edge_to_unique: Optional[np.ndarray] = None,
) -> BlockedCSRDev:
    """Compose dst-sorted edge_map with perm_dst -> canonical edge indices.

    With ``edge_to_unique`` given, also precompute the slot -> compact-row
    map (``edge_map_unique``), so traversal kernels can gather COMPACT-layout
    messages straight from the unique-pair table in-kernel.
    """
    valid = bc.edge_map >= 0
    edge_map = np.full(bc.edge_map.shape, -1, np.int32)
    edge_map[valid] = np.asarray(perm_dst)[bc.edge_map[valid]]
    if edge_to_unique is None:
        edge_map_u = edge_map
    else:
        edge_map_u = np.full(bc.edge_map.shape, -1, np.int32)
        edge_map_u[valid] = np.asarray(edge_to_unique)[edge_map[valid]]
    t = bc.num_tiles
    return BlockedCSRDev(
        edge_map=jnp.asarray(edge_map),
        edge_map_unique=jnp.asarray(edge_map_u),
        local_dst=jnp.asarray(bc.local_dst.reshape(t, bc.edge_tile)),
        t2b=jnp.asarray(bc.tile_to_block),
        edge_tile=bc.edge_tile,
        node_block=bc.node_block,
        num_node_blocks=bc.num_node_blocks,
        num_nodes=bc.num_nodes,
    )


def msg_rev_dev(msg_rows: np.ndarray, num_rows: int,
                edge_tile: int,
                target_edges: Optional[int] = None) -> BlockedCSRDev:
    """The message-row layout (``layout.msg_rows_csr``) on the device, with
    canonical edge indices; ``target_edges`` grows it with pad tiles
    (``layout.pad_blocked_csr``)."""
    bc, perm = L.msg_rows_csr(msg_rows, num_rows, edge_tile)
    if target_edges is not None:
        bc = L.pad_blocked_csr(bc, target_edges)
    return blocked_csr_dev(bc, perm)


@dataclasses.dataclass(frozen=True)
class _Static:
    """Wrap static metadata (e.g. shape tuples) riding inside custom_vjp
    residuals: the payload lives in the pytree *treedef*, so it stays a
    plain Python value under jit instead of becoming a traced leaf."""

    value: tuple


jtu.register_pytree_node(
    _Static, lambda s: ((), s.value), lambda aux, _: _Static(aux))


# ---------------------------------------------------------------------------
# the tile padding permutation: compact rows <-> tile-padded rows
#
# By the invariant of ``PaddedSegmentsDev``, padding is a permutation that
# adds zero rows and un-padding one that drops them, each the other's exact
# transpose. The custom VJPs say so, so the backward moves rows with a
# gather, not with the scatter-add into zeros that XLA derives for a gather.
# ---------------------------------------------------------------------------
def _float0(a: jnp.ndarray) -> np.ndarray:
    return np.zeros(a.shape, dtype=jax.dtypes.float0)


@jax.custom_vjp
def _pad(x, row_map, inv_map):
    valid = row_map >= 0
    xp = x[jnp.maximum(row_map, 0)]
    if x.ndim == 1:
        return jnp.where(valid, xp, 0.0)
    return jnp.where(valid[:, None], xp, 0.0)


@jax.custom_vjp
def _unpad(y_p, row_map, inv_map):
    return y_p[inv_map]


def _pad_fwd(x, row_map, inv_map):
    return _pad(x, row_map, inv_map), (row_map, inv_map)


def _pad_bwd(res, dy_p):
    row_map, inv_map = res
    # pad slots held a constant: they carry no gradient and are dropped
    return _unpad(dy_p, row_map, inv_map), _float0(row_map), _float0(inv_map)


def _unpad_fwd(y_p, row_map, inv_map):
    return _unpad(y_p, row_map, inv_map), (row_map, inv_map)


def _unpad_bwd(res, dy):
    row_map, inv_map = res
    return _pad(dy, row_map, inv_map), _float0(row_map), _float0(inv_map)


_pad.defvjp(_pad_fwd, _pad_bwd)
_unpad.defvjp(_unpad_fwd, _unpad_bwd)


def _count_perm(op: str) -> None:
    """Runs where the op is traced (on the host, once per trace under jit)."""
    obs.metrics().counter("padded_perm_traced", op=op).inc()


def pad_rows(x: jnp.ndarray, lay: PaddedSegmentsDev) -> jnp.ndarray:
    """Compact rows [M, ...] -> tile-padded rows [Rp, ...]; pad rows are 0.
    Its VJP is ``unpad_rows``."""
    _count_perm("pad")
    return _pad(x, lay.row_map, lay.inv_map)


def unpad_rows(y_p: jnp.ndarray, lay: PaddedSegmentsDev) -> jnp.ndarray:
    """Tile-padded rows [Rp, ...] -> compact rows [M, ...] (``y_p[inv_map]``).
    Its VJP is ``pad_rows``."""
    _count_perm("unpad")
    return _unpad(y_p, lay.row_map, lay.inv_map)


# ---------------------------------------------------------------------------
# the compact message gather ``msg[msg_rows]`` and its sum by message row
#
# ``msg_rows`` repeats rows, so the gather's transpose is a reduction, not a
# permutation. XLA writes it as a scatter-add into the compact table; here
# the backward sums on the traversal template instead, over the edges
# sorted by message row (``msg_rev_dev``).
# ---------------------------------------------------------------------------
def _gather_heads(msg: jnp.ndarray, msg_rows: jnp.ndarray,
                  heads: int) -> jnp.ndarray:
    """``msg[msg_rows]``; with ``heads`` > 1 the head-major per-edge rows
    ``_heads_major(msg)[h*Em + msg_rows]`` -> ``[H*E, d/H]``."""
    if heads == 1:
        return msg[msg_rows]
    return _heads_major(msg, heads)[_shift(msg_rows, heads, msg.shape[0])]


@functools.lru_cache(maxsize=None)
def _make_gather_msg_rows(row_block: int, num_row_blocks: int, heads: int,
                          interpret: bool):
    @jax.custom_vjp
    def f(msg, msg_rows, edge_map, local_row, t2b):
        return _gather_heads(msg, msg_rows, heads)

    def fwd(msg, msg_rows, edge_map, local_row, t2b):
        shapes = _Static((msg.shape[0], msg_rows.shape))
        return f(msg, msg_rows, edge_map, local_row, t2b), (
            edge_map, local_row, t2b, shapes)

    def bwd(res, dy):
        edge_map, local_row, t2b, shapes = res
        em, mr = shapes.value
        dh = dy.shape[-1]
        # the cotangent in sorted, padded slot order, edges on lanes and
        # every head's columns stacked: one pass sums all heads of a row
        dy_h = dy.reshape(heads, mr[0], dh)
        g_p = jnp.where((edge_map >= 0)[None, :, None],
                        dy_h[:, jnp.maximum(edge_map, 0)], 0.0)
        g_p = g_p.transpose(0, 2, 1).reshape(heads * dh, -1)
        out = TK.seg_row_sum_padded(g_p, local_row, t2b, row_block=row_block,
                                    num_row_blocks=num_row_blocks,
                                    interpret=interpret)
        dmsg = out[:, :em].reshape(heads, dh, em).transpose(2, 0, 1)
        f0 = jax.dtypes.float0
        return (dmsg.reshape(em, heads * dh), np.zeros(mr, f0),
                _float0(edge_map), _float0(local_row), _float0(t2b))

    f.defvjp(fwd, bwd)
    return f


def gather_msg_rows(msg: jnp.ndarray, msg_rows: jnp.ndarray,
                    rev: Optional[BlockedCSRDev],
                    backend: Backend = "pallas",
                    heads: int = 1) -> jnp.ndarray:
    """``msg[msg_rows]``: compact message rows ``[Em, d]`` -> per edge
    ``[E, d]``; with ``heads`` the head-major rows ``[H*E, d/H]`` of
    ``_per_head``. On the Pallas backends with the message-row layout
    ``rev`` its VJP sums each row's edges, all heads at once, with
    ``traversal.seg_row_sum_padded``; without it (or on ``xla``) XLA
    derives its scatter-add."""
    if rev is None or backend == "xla" or msg_rows.shape[0] == 0:
        return _gather_heads(msg, msg_rows, heads)
    f = _make_gather_msg_rows(rev.node_block, rev.num_node_blocks, heads,
                              backend == "pallas_interpret")
    return f(msg, msg_rows, rev.edge_map, rev.local_dst, rev.t2b)


def _gather_msg(op: str, msg, msg_rows, rev, backend,
                heads: int = 1) -> jnp.ndarray:
    """An unfused aggregation's message gather, counted where it is traced
    by the backward it gets (``path``: ``kernel`` or XLA's ``scatter``)."""
    if msg_rows is None:
        return msg if heads == 1 else _heads_major(msg, heads)
    obs.metrics().counter("msg_row_sum_traced", op=op,
                          path="scatter" if rev is None else "kernel").inc()
    return gather_msg_rows(msg, msg_rows, rev, backend, heads)


# ---------------------------------------------------------------------------
# segment MM (the GEMM template)
# ---------------------------------------------------------------------------
def _segment_mm_xla_padded(x_p, w, t2g, scale_p, tile):
    t = t2g.shape[0]
    xt = x_p.reshape(t, tile, x_p.shape[-1])
    wt = w[t2g]                                    # [T, k, n]
    y = jnp.einsum("tck,tkn->tcn", xt, wt,
                   preferred_element_type=jnp.float32)
    y = y.reshape(t * tile, -1).astype(x_p.dtype)
    if scale_p is not None:
        y = y * scale_p
    return y


def _fit_tile_n(n: int, tile_n: int) -> int:
    """Column tile the TPU accepts: ``tile_n`` capped at ``n`` when that is
    ``n`` itself or a multiple of the 128-lane tile dividing ``n``; else
    the whole of ``n``."""
    tn = min(tile_n, n)
    return tn if tn == n or (tn % 128 == 0 and n % tn == 0) else n


def _fit_tile_rows(lay_tile: int, tile_rows: Optional[int]) -> int:
    """Effective kernel row tile: a requested sub-tile of the layout tile
    (each sub-tile then still lies within one type segment), or the layout
    tile itself when unset/incompatible."""
    if tile_rows is None or tile_rows <= 0 or lay_tile % tile_rows:
        return lay_tile
    return tile_rows


def _subtile_t2g(t2g: jnp.ndarray, lay_tile: int, tile_rows: int):
    """Expand the tile->group map to sub-tile granularity (each layout tile
    splits into ``lay_tile // tile_rows`` kernel tiles of the same group,
    so the map stays non-decreasing and group-aligned)."""
    if tile_rows == lay_tile:
        return t2g
    return jnp.repeat(t2g, lay_tile // tile_rows)


@functools.lru_cache(maxsize=None)
def _make_pallas_segment_mm(tile_rows: int, tile_n: int, num_groups: int,
                            with_scale: bool, interpret: bool):
    kw = dict(tile_rows=tile_rows, tile_n=tile_n, interpret=interpret)

    @jax.custom_vjp
    def f(x_p, w, scale_p, t2g):
        y = SK.segment_mm_padded(x_p, w, t2g, scale_p if with_scale else None,
                                 **kw)
        return y

    def fwd(x_p, w, scale_p, t2g):
        y_pre = SK.segment_mm_padded(x_p, w, t2g, None, **kw)
        y = y_pre * scale_p if with_scale else y_pre
        return y, (x_p, w, scale_p, t2g, y_pre)

    def bwd(res, dy):
        x_p, w, scale_p, t2g, y_pre = res
        dys = dy * scale_p if with_scale else dy
        w_t = jnp.swapaxes(w, 1, 2)
        dx = SK.segment_mm_padded(
            dys, w_t, t2g, None,
            tile_rows=tile_rows, tile_n=_fit_tile_n(w.shape[1], tile_n),
            interpret=interpret,
        )
        dw = SK.segment_outer_padded(
            x_p, dys, t2g, num_groups=num_groups, tile_rows=tile_rows,
            interpret=interpret,
        )
        # groups with zero rows own no tiles -> their dW block is never
        # visited (uninitialized); mask them to exact zeros.
        present = compat.segment_sum(
            jnp.ones_like(t2g), t2g, num_groups
        ) > 0
        dw = jnp.where(present[:, None, None], dw, 0.0).astype(w.dtype)
        if with_scale:
            dscale = jnp.sum(dy * y_pre, axis=1, keepdims=True).astype(scale_p.dtype)
        else:
            dscale = jnp.zeros_like(scale_p)
        dt2g = np.zeros(t2g.shape, dtype=jax.dtypes.float0)
        return dx, dw, dscale, dt2g

    f.defvjp(fwd, bwd)
    return f


def _padded_mm(x_p, w, t2g, scale_p, tile_rows, num_groups, backend,
               tile_n):
    """The GEMM template over tile-padded rows: ``Y_p = X_p @ W[t2g]``."""
    if backend == "xla":
        return _segment_mm_xla_padded(x_p, w, t2g, scale_p, tile_rows)
    interpret = backend == "pallas_interpret"
    tn = _fit_tile_n(w.shape[-1], tile_n)
    f = _make_pallas_segment_mm(tile_rows, tn, num_groups,
                                scale_p is not None, interpret)
    if scale_p is None:
        scale_p = jnp.ones((x_p.shape[0], 1), x_p.dtype)
    return f(x_p, w, scale_p, t2g)


def _count_heads(op: str, heads: int) -> None:
    """Runs where a per-head op is traced (on the host, once per trace)."""
    obs.metrics().counter("heads_traced", op=op, heads=str(heads)).inc()


def _heads_major(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[M, H*d]`` -> ``[H*M, d]``: head ``h`` of row ``m`` becomes row
    ``h*M + m``."""
    m = x.shape[0]
    return x.reshape(m, heads, -1).transpose(1, 0, 2).reshape(heads * m, -1)


def _rows_major(y: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[H*M, d]`` -> ``[M, H*d]``, the inverse of ``_heads_major``."""
    m = y.shape[0] // heads
    return y.reshape(heads, m, -1).transpose(1, 0, 2).reshape(m, -1)


def segment_mm(
    x_sorted: jnp.ndarray,                  # [M, k] or [M, H*k] type-sorted
    w: jnp.ndarray,                         # [R, k, n] or [R, H, k, n]
    lay: PaddedSegmentsDev,
    row_scale: Optional[jnp.ndarray] = None,  # [M]
    backend: Backend = "xla",
    tile_n: int = 128,
    tile_rows: Optional[int] = None,        # sub-tile of lay.tile (tuner knob)
) -> jnp.ndarray:
    """Y = X @ W[type] (+ per-row scale), X presorted by type. -> [M, n].

    With per-head weights ``[R, H, k, n]`` each row's ``h``-th ``k``-wide
    block is multiplied by its type's ``[k, n]`` matrix of head ``h``
    -> ``[M, H*n]``. The heads become rows: padded rows go head-major
    (``[H*Rp, k]``), each head's tiles keep their type and select weight
    group ``h*R + type``, and the one GEMM template runs over all of them,
    so no block-diagonal matrix is formed."""
    if x_sorted.shape[0] == 0:
        # empty block (e.g. a sampled hop with no edges): no tiles to sweep
        n = w.shape[-1] * (w.shape[1] if w.ndim == 4 else 1)
        return jnp.zeros((0, n), x_sorted.dtype)
    x_p = pad_rows(x_sorted, lay)
    scale_p = None
    if row_scale is not None:
        scale_p = pad_rows(row_scale, lay)[:, None]
    tr = _fit_tile_rows(lay.tile, tile_rows)
    t2g = _subtile_t2g(lay.t2g, lay.tile, tr)
    if w.ndim == 3:
        y_p = _padded_mm(x_p, w, t2g, scale_p, tr, lay.num_groups, backend,
                         tile_n)
        return unpad_rows(y_p, lay)
    r, heads = w.shape[:2]
    _count_heads("gemm", heads)
    w_h = w.transpose(1, 0, 2, 3).reshape(heads * r, *w.shape[2:])
    t2g_h = (t2g[None, :] + r * jnp.arange(heads, dtype=t2g.dtype)[:, None]
             ).reshape(-1)
    if scale_p is not None:
        scale_p = jnp.tile(scale_p, (heads, 1))
    y_h = _padded_mm(_heads_major(x_p, heads), w_h, t2g_h, scale_p, tr,
                     heads * r, backend, tile_n)
    return unpad_rows(_rows_major(y_h, heads), lay)


def gather_mm(
    feats: jnp.ndarray,                     # [N, k] node features
    w: jnp.ndarray,                         # [R, k, n]
    gather_idx: jnp.ndarray,                # [M] e.g. src / unique_src
    lay: PaddedSegmentsDev,
    row_scale: Optional[jnp.ndarray] = None,
    backend: Backend = "xla",
) -> jnp.ndarray:
    """Full GEMM template: Y = X[G] @ W[T] (+ scale). Gather runs as an XLA
    fused gather feeding the kernel (TPU adaptation, DESIGN.md §3)."""
    return segment_mm(feats[gather_idx], w, lay, row_scale, backend)


@functools.lru_cache(maxsize=None)
def _make_pallas_segment_mm_gather(tile_rows: int, tile_n: int,
                                   num_groups: int, with_scale: bool,
                                   interpret: bool):
    kw = dict(tile_rows=tile_rows, tile_n=tile_n, interpret=interpret)

    @jax.custom_vjp
    def f(x, w, scale_p, gidx, t2g):
        return SK.segment_mm_gather_padded(
            x, w, gidx, t2g, scale_p if with_scale else None, **kw)

    def fwd(x, w, scale_p, gidx, t2g):
        y_pre = SK.segment_mm_gather_padded(x, w, gidx, t2g, None, **kw)
        y = y_pre * scale_p if with_scale else y_pre
        return y, (x, w, scale_p, gidx, t2g, y_pre)

    def bwd(res, dy):
        x, w, scale_p, gidx, t2g, y_pre = res
        dys = dy * scale_p if with_scale else dy
        w_t = jnp.swapaxes(w, 1, 2)
        # template-derived backward: a GEMM instance over padded dY rows,
        # then the gather access scheme transposes into a scatter-add that
        # routes each padded row's gradient back to its source row.
        dxg = SK.segment_mm_padded(
            dys, w_t, t2g, None,
            tile_rows=tile_rows, tile_n=_fit_tile_n(w.shape[1], tile_n),
            interpret=interpret,
        )
        valid = gidx >= 0
        dx = jnp.zeros_like(x).at[jnp.where(valid, gidx, 0)].add(
            jnp.where(valid[:, None], dxg, 0.0).astype(x.dtype))
        # dW needs X in padded-row order; materialized here only, i.e. only
        # on the training path — the forward/serving path never builds it.
        x_p = jnp.where(valid[:, None], x[jnp.maximum(gidx, 0)], 0)
        dw = SK.segment_outer_padded(
            x_p, dys, t2g, num_groups=num_groups, tile_rows=tile_rows,
            interpret=interpret,
        )
        present = compat.segment_sum(jnp.ones_like(t2g), t2g, num_groups) > 0
        dw = jnp.where(present[:, None, None], dw, 0.0).astype(w.dtype)
        if with_scale:
            dscale = jnp.sum(dy * y_pre, axis=1,
                             keepdims=True).astype(scale_p.dtype)
        else:
            dscale = jnp.zeros_like(scale_p)
        f0 = jax.dtypes.float0
        return (dx, dw, dscale, np.zeros(gidx.shape, f0),
                np.zeros(t2g.shape, f0))

    f.defvjp(fwd, bwd)
    return f


def segment_mm_gather(
    x_src: jnp.ndarray,                     # [Nx, k] ungathered source rows
    w: jnp.ndarray,                         # [R, k, n]
    lay: PaddedSegmentsDev,
    gather_rows: jnp.ndarray,               # [Rp] slot -> source row, or -1
    row_scale: Optional[jnp.ndarray] = None,  # [M] canonical per-row scale
    backend: Backend = "xla",
    tile_n: int = 128,
    tile_rows: Optional[int] = None,        # sub-tile of lay.tile (tuner knob)
) -> jnp.ndarray:
    """Y = X[G] @ W[type] with the gather folded into the kernel. -> [M, n].

    ``gather_rows`` is the padded gather-index layout
    (``layout.compose_gather_rows``): it composes the access-scheme gather
    list (edge src / edge dst / unique src) with the tile padding map, so on
    the Pallas backends the ``[M, k]``/``[Rp, k]`` input copy that
    ``gather_mm`` materializes never exists — each kernel grid step reads
    its rows straight out of the VMEM-resident source block. The XLA
    backend keeps the materialized formulation (XLA fuses the gather
    itself).
    """
    n = w.shape[-1]
    m = int(lay.inv_map.shape[0])
    if m == 0:
        # empty block (e.g. a sampled hop with no edges): no tiles to sweep
        return jnp.zeros((0, n), x_src.dtype)
    scale_p = None
    if row_scale is not None:
        scale_p = pad_rows(row_scale, lay)[:, None]
    tr = _fit_tile_rows(lay.tile, tile_rows)
    t2g = _subtile_t2g(lay.t2g, lay.tile, tr)
    if backend == "xla":
        valid = gather_rows >= 0
        x_p = jnp.where(valid[:, None],
                        x_src[jnp.maximum(gather_rows, 0)], 0)
        y_p = _segment_mm_xla_padded(x_p, w, t2g, scale_p, tr)
    else:
        interpret = backend == "pallas_interpret"
        tn = _fit_tile_n(n, tile_n)
        f = _make_pallas_segment_mm_gather(tr, tn, lay.num_groups,
                                           scale_p is not None, interpret)
        if scale_p is None:
            scale_p = jnp.ones((gather_rows.shape[0], 1), x_src.dtype)
        y_p = f(x_src, w, scale_p, gather_rows, t2g)
    return unpad_rows(y_p, lay)


# ---------------------------------------------------------------------------
# traversal ops
# ---------------------------------------------------------------------------
def _pad_edges(x: jnp.ndarray, bc: BlockedCSRDev, fill: float) -> jnp.ndarray:
    """Canonical edge tensor -> padded dst-sorted layout."""
    valid = bc.edge_map >= 0
    xp = x[jnp.maximum(bc.edge_map, 0)]
    if x.ndim == 1:
        xp = jnp.where(valid, xp, fill)
        return xp.reshape(-1, bc.edge_tile)
    return jnp.where(valid[:, None], xp, fill)


@functools.lru_cache(maxsize=None)
def _make_pallas_softmax_agg(node_block: int, num_node_blocks: int,
                             num_nodes: int, interpret: bool):
    kw = dict(node_block=node_block, num_node_blocks=num_node_blocks,
              interpret=interpret)

    @jax.custom_vjp
    def f(scores, msg, dst, bc_edge_map, bc_local_dst, bc_t2b):
        scores_p = jnp.where(
            bc_edge_map >= 0, scores[jnp.maximum(bc_edge_map, 0)], TK._NEG_INF
        ).reshape(-1, bc_local_dst.shape[-1])
        msg_p = jnp.where(
            (bc_edge_map >= 0)[:, None],
            msg[jnp.maximum(bc_edge_map, 0)], 0.0,
        )
        mx, den = TK.seg_stats_padded(scores_p, bc_local_dst, bc_t2b, **kw)
        out = TK.seg_softmax_agg_padded(
            scores_p, msg_p, bc_local_dst, bc_t2b, mx, den, **kw
        )
        return out[:num_nodes]

    def fwd(scores, msg, dst, bc_edge_map, bc_local_dst, bc_t2b):
        shapes = _Static((bc_edge_map.shape, bc_local_dst.shape,
                          bc_t2b.shape))
        out = f(scores, msg, dst, bc_edge_map, bc_local_dst, bc_t2b)
        att = R.edge_softmax_ref(scores, dst, num_nodes)
        return out, (att, msg, dst, shapes)

    def bwd_full(res, dout):
        att, msg, dst, shapes = res
        g = dout[dst]
        dmsg = (att[:, None] * g).astype(msg.dtype)
        datt = jnp.sum(msg * g, axis=-1)
        c = compat.segment_sum(att * datt, dst, num_nodes)
        dscores = (att * (datt - c[dst])).astype(att.dtype)
        f0 = jax.dtypes.float0
        em, ld, tb = shapes.value
        return (
            dscores, dmsg,
            np.zeros(dst.shape, dtype=f0),
            np.zeros(em, dtype=f0),
            np.zeros(ld, dtype=f0),
            np.zeros(tb, dtype=f0),
        )

    f.defvjp(fwd, bwd_full)
    return f


@functools.lru_cache(maxsize=None)
def _make_pallas_softmax_agg_gather(node_block: int, num_node_blocks: int,
                                    num_nodes: int, identity_rows: bool,
                                    interpret: bool):
    """``identity_rows=True`` specializes for canonical-order messages:
    the backward computes dmsg directly instead of an identity
    gather/scatter pair."""
    kw = dict(node_block=node_block, num_node_blocks=num_node_blocks,
              interpret=interpret)

    @jax.custom_vjp
    def f(scores, msg, dst, msg_rows, bc_edge_map, mmap, bc_local_dst,
          bc_t2b):
        # scores are 1-D scalars: padding them stays outside the kernel
        # (cheap); the feature-wide message gather moves inside it.
        scores_p = jnp.where(
            bc_edge_map >= 0, scores[jnp.maximum(bc_edge_map, 0)],
            TK._NEG_INF,
        ).reshape(-1, bc_local_dst.shape[-1])
        mx, den = TK.seg_stats_padded(scores_p, bc_local_dst, bc_t2b, **kw)
        out = TK.seg_softmax_agg_gather_padded(
            scores_p, msg, mmap, bc_local_dst, bc_t2b, mx, den, **kw
        )
        return out[:num_nodes]

    def fwd(scores, msg, dst, msg_rows, bc_edge_map, mmap, bc_local_dst,
            bc_t2b):
        shapes = _Static((msg_rows.shape, bc_edge_map.shape, mmap.shape,
                          bc_local_dst.shape, bc_t2b.shape))
        out = f(scores, msg, dst, msg_rows, bc_edge_map, mmap,
                bc_local_dst, bc_t2b)
        att = R.edge_softmax_ref(scores, dst, num_nodes)
        return out, (att, msg, dst, msg_rows, shapes)

    def bwd(res, dout):
        att, msg, dst, msg_rows, shapes = res
        g = dout[dst]                                # [E, d]
        contrib = (att[:, None] * g).astype(msg.dtype)
        if identity_rows:
            msg_e = msg
            dmsg = contrib
        else:                                        # training path only
            msg_e = jnp.take(msg, msg_rows, axis=0)
            dmsg = jnp.zeros_like(msg).at[msg_rows].add(contrib)
        datt = jnp.sum(msg_e * g, axis=-1)
        c = compat.segment_sum(att * datt, dst, num_nodes)
        dscores = (att * (datt - c[dst])).astype(att.dtype)
        f0 = jax.dtypes.float0
        mr, em, mm, ld, tb = shapes.value
        return (dscores, dmsg,
                np.zeros(dst.shape, f0), np.zeros(mr, f0),
                np.zeros(em, f0), np.zeros(mm, f0),
                np.zeros(ld, f0), np.zeros(tb, f0))

    f.defvjp(fwd, bwd)
    return f


def _msg_slot_map(bc: BlockedCSRDev,
                  msg_rows: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Padded slot -> message-row map for in-kernel message gathers."""
    if msg_rows is None:
        return bc.edge_map
    return jnp.where(
        bc.edge_map >= 0, msg_rows[jnp.maximum(bc.edge_map, 0)], -1)


def _shift(idx: jnp.ndarray, heads: int, stride: int) -> jnp.ndarray:
    """An index map repeated per head, head ``h`` shifted by ``h*stride``
    (-1 entries stay -1)."""
    off = stride * jnp.arange(heads, dtype=idx.dtype)[:, None]
    return jnp.where(idx[None] >= 0, idx[None] + off, -1).reshape(-1)


def _per_head(fn, op: str, scores, msg, dst, num_nodes, bc, backend,
              msg_rows, msg_slot_map, fuse_gather, msg_rev) -> jnp.ndarray:
    """Run the one-head traversal op ``fn`` over ``[E, H]`` score columns
    as one graph of ``H * Np`` destinations (``Np`` the blocked layout's
    padded node count): head ``h`` of node ``v`` is node ``h*Np + v``, of
    edge ``e`` edge ``h*E + e``, of message row ``i`` row ``h*Em + i``
    (its ``h``-th ``d/H`` columns). The blocked layout repeats once per
    head with its node blocks shifted by ``h*Np``, so the kernels and their
    VJPs run the heads as they run nodes. An unfused message gather runs
    here (``gather_msg_rows``), so its backward sums all heads of a message
    row in one pass over the one-head message-row layout."""
    heads = scores.shape[1]
    _count_heads(op, heads)
    e, em = dst.shape[0], msg.shape[0]
    np_ = bc.num_node_blocks * bc.node_block
    bc_h = BlockedCSRDev(
        edge_map=_shift(bc.edge_map, heads, e),
        edge_map_unique=_shift(bc.edge_map_unique, heads, em),
        local_dst=jnp.tile(bc.local_dst, (heads, 1)),
        t2b=(bc.t2b[None] + bc.num_node_blocks * jnp.arange(
            heads, dtype=bc.t2b.dtype)[:, None]).reshape(-1),
        edge_tile=bc.edge_tile, node_block=bc.node_block,
        num_node_blocks=heads * bc.num_node_blocks,
        num_nodes=heads * np_)
    if fuse_gather:
        msg_h = _heads_major(msg, heads)
        rows = None if msg_rows is None else _shift(msg_rows, heads, em)
        slots = None if msg_slot_map is None else _shift(msg_slot_map,
                                                        heads, em)
    else:
        msg_h = _gather_msg(op, msg, msg_rows, msg_rev, backend, heads)
        rows = slots = None
    out = fn(scores.T.reshape(-1), msg_h,
             _shift(dst, heads, np_), heads * np_, bc=bc_h, backend=backend,
             msg_rows=rows, msg_slot_map=slots, fuse_gather=fuse_gather)
    out = out.reshape(heads, np_, -1)[:, :num_nodes]
    return _rows_major(out.reshape(heads * num_nodes, -1), heads)


def _heads_ref(agg, scores, msg, msg_rows, dst, num_nodes):
    """XLA formulation with H score columns: head ``h`` of a message is its
    ``h``-th block of ``d/H`` columns."""
    msg_e = msg if msg_rows is None else msg[msg_rows]
    out = agg(scores, msg_e.reshape(msg_e.shape[0], scores.shape[1], -1),
              dst, num_nodes)
    return out.reshape(num_nodes, -1)


def edge_softmax_agg(
    scores: jnp.ndarray,        # [E] canonical order, or [E, H] per head
    msg: jnp.ndarray,           # [Em, d] in storage order (see msg_rows)
    dst: jnp.ndarray,           # [E] canonical destination ids
    num_nodes: int,
    bc: Optional[BlockedCSRDev] = None,
    backend: Backend = "xla",
    msg_rows: Optional[jnp.ndarray] = None,   # [E] edge -> msg row, or None
    msg_slot_map: Optional[jnp.ndarray] = None,  # [Ep] precomposed slot map
    fuse_gather: bool = True,
    msg_rev: Optional[BlockedCSRDev] = None,  # message-row layout of msg_rows
) -> jnp.ndarray:
    """out[v] = Σ_{e→v} softmax(scores)_e · msg_e — the fused traversal region.

    ``msg_rows`` lets messages live in a compact storage (e.g. the unique
    (src, etype) table with ``edge_to_unique`` as the map); with
    ``fuse_gather`` (Pallas backends) the per-edge message gather happens
    inside the kernel via the slot map, so no dst-sorted ``[Ep, d]`` copy is
    materialized. ``fuse_gather=False`` keeps the materialized-gather kernel
    (equivalence baseline); its gather ``msg[msg_rows]`` sums its gradient
    by message row over ``msg_rev`` (``gather_msg_rows``).

    ``[E, H]`` scores are H heads: softmax per column, and head ``h``
    weighs the ``h``-th ``d/H``-wide block of each message. On the Pallas
    backends the heads run as the nodes of one larger graph
    (``_per_head``).
    """
    if dst.shape[0] == 0:
        return jnp.zeros((num_nodes, msg.shape[-1]), msg.dtype)
    if scores.ndim == 2:
        if backend == "xla" or bc is None:
            return _heads_ref(R.softmax_agg_ref, scores, msg, msg_rows, dst,
                              num_nodes)
        return _per_head(edge_softmax_agg, "softmax_agg", scores, msg, dst,
                         num_nodes, bc, backend, msg_rows, msg_slot_map,
                         fuse_gather, msg_rev)
    if backend == "xla" or bc is None:
        msg_e = msg if msg_rows is None else msg[msg_rows]
        return R.softmax_agg_ref(scores, msg_e, dst, num_nodes)
    interpret = backend == "pallas_interpret"
    if fuse_gather:
        rows = (msg_rows if msg_rows is not None
                else jnp.arange(dst.shape[0], dtype=jnp.int32))
        if msg_slot_map is None:
            msg_slot_map = _msg_slot_map(bc, msg_rows)
        f = _make_pallas_softmax_agg_gather(bc.node_block,
                                            bc.num_node_blocks,
                                            num_nodes, msg_rows is None,
                                            interpret)
        return f(scores, msg, dst, rows, bc.edge_map, msg_slot_map,
                 bc.local_dst, bc.t2b)
    msg_e = _gather_msg("softmax_agg", msg, msg_rows, msg_rev, backend)
    f = _make_pallas_softmax_agg(bc.node_block, bc.num_node_blocks,
                                 num_nodes, interpret)
    return f(scores, msg_e, dst, bc.edge_map, bc.local_dst, bc.t2b)


@functools.lru_cache(maxsize=None)
def _make_pallas_weighted_agg(node_block: int, num_node_blocks: int,
                              num_nodes: int, interpret: bool):
    kw = dict(node_block=node_block, num_node_blocks=num_node_blocks,
              interpret=interpret)

    @jax.custom_vjp
    def f(scale, msg, dst, bc_edge_map, bc_local_dst, bc_t2b):
        scale_p = jnp.where(
            bc_edge_map >= 0, scale[jnp.maximum(bc_edge_map, 0)], 0.0
        ).reshape(-1, bc_local_dst.shape[-1])
        msg_p = jnp.where(
            (bc_edge_map >= 0)[:, None],
            msg[jnp.maximum(bc_edge_map, 0)], 0.0,
        )
        out = TK.seg_weighted_agg_padded(scale_p, msg_p, bc_local_dst,
                                         bc_t2b, **kw)
        return out[:num_nodes]

    def fwd(scale, msg, dst, bc_edge_map, bc_local_dst, bc_t2b):
        shapes = _Static((bc_edge_map.shape, bc_local_dst.shape,
                          bc_t2b.shape))
        out = f(scale, msg, dst, bc_edge_map, bc_local_dst, bc_t2b)
        return out, (scale, msg, dst, shapes)

    def bwd(res, dout):
        scale, msg, dst, shapes = res
        g = dout[dst]
        dmsg = (scale[:, None] * g).astype(msg.dtype)
        dscale = jnp.sum(msg * g, axis=-1).astype(scale.dtype)
        f0 = jax.dtypes.float0
        em, ld, tb = shapes.value
        return (dscale, dmsg, np.zeros(dst.shape, f0),
                np.zeros(em, f0), np.zeros(ld, f0), np.zeros(tb, f0))

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _make_pallas_weighted_agg_gather(node_block: int, num_node_blocks: int,
                                     num_nodes: int, identity_rows: bool,
                                     interpret: bool):
    kw = dict(node_block=node_block, num_node_blocks=num_node_blocks,
              interpret=interpret)

    @jax.custom_vjp
    def f(scale, msg, dst, msg_rows, bc_edge_map, mmap, bc_local_dst,
          bc_t2b):
        scale_p = jnp.where(
            bc_edge_map >= 0, scale[jnp.maximum(bc_edge_map, 0)], 0.0
        ).reshape(-1, bc_local_dst.shape[-1])
        out = TK.seg_weighted_agg_gather_padded(
            scale_p, msg, mmap, bc_local_dst, bc_t2b, **kw)
        return out[:num_nodes]

    def fwd(scale, msg, dst, msg_rows, bc_edge_map, mmap, bc_local_dst,
            bc_t2b):
        shapes = _Static((msg_rows.shape, bc_edge_map.shape, mmap.shape,
                          bc_local_dst.shape, bc_t2b.shape))
        out = f(scale, msg, dst, msg_rows, bc_edge_map, mmap, bc_local_dst,
                bc_t2b)
        return out, (scale, msg, dst, msg_rows, shapes)

    def bwd(res, dout):
        scale, msg, dst, msg_rows, shapes = res
        g = dout[dst]
        contrib = (scale[:, None] * g).astype(msg.dtype)
        if identity_rows:
            msg_e = msg
            dmsg = contrib
        else:                                        # training path only
            msg_e = jnp.take(msg, msg_rows, axis=0)
            dmsg = jnp.zeros_like(msg).at[msg_rows].add(contrib)
        dscale = jnp.sum(msg_e * g, axis=-1).astype(scale.dtype)
        f0 = jax.dtypes.float0
        mr, em, mm, ld, tb = shapes.value
        return (dscale, dmsg,
                np.zeros(dst.shape, f0), np.zeros(mr, f0),
                np.zeros(em, f0), np.zeros(mm, f0),
                np.zeros(ld, f0), np.zeros(tb, f0))

    f.defvjp(fwd, bwd)
    return f


def weighted_agg(
    scale: Optional[jnp.ndarray],   # [E] or None
    msg: jnp.ndarray,               # [Em, d] in storage order (see msg_rows)
    dst: jnp.ndarray,
    num_nodes: int,
    bc: Optional[BlockedCSRDev] = None,
    backend: Backend = "xla",
    msg_rows: Optional[jnp.ndarray] = None,
    msg_slot_map: Optional[jnp.ndarray] = None,
    fuse_gather: bool = True,
    msg_rev: Optional[BlockedCSRDev] = None,
) -> jnp.ndarray:
    """out[v] = Σ_{e→v} scale_e · msg_e (gather semantics as edge_softmax_agg;
    an ``[E, H]`` scale weighs each message's H column blocks)."""
    if dst.shape[0] == 0:
        return jnp.zeros((num_nodes, msg.shape[-1]), msg.dtype)
    if scale is not None and scale.ndim == 2:
        if backend == "xla" or bc is None:
            return _heads_ref(R.weighted_agg_ref, scale, msg, msg_rows, dst,
                              num_nodes)
        return _per_head(weighted_agg, "weighted_agg", scale, msg, dst,
                         num_nodes, bc, backend, msg_rows, msg_slot_map,
                         fuse_gather, msg_rev)
    if backend == "xla" or bc is None:
        msg_e = msg if msg_rows is None else msg[msg_rows]
        return R.weighted_agg_ref(scale, msg_e, dst, num_nodes)
    if scale is None:
        scale = jnp.ones(dst.shape[0], msg.dtype)
    interpret = backend == "pallas_interpret"
    if fuse_gather:
        rows = (msg_rows if msg_rows is not None
                else jnp.arange(dst.shape[0], dtype=jnp.int32))
        if msg_slot_map is None:
            msg_slot_map = _msg_slot_map(bc, msg_rows)
        f = _make_pallas_weighted_agg_gather(bc.node_block,
                                             bc.num_node_blocks,
                                             num_nodes, msg_rows is None,
                                             interpret)
        return f(scale, msg, dst, rows, bc.edge_map, msg_slot_map,
                 bc.local_dst, bc.t2b)
    msg_e = _gather_msg("weighted_agg", msg, msg_rows, msg_rev, backend)
    f = _make_pallas_weighted_agg(bc.node_block, bc.num_node_blocks,
                                  num_nodes, interpret)
    return f(scale, msg_e, dst, bc.edge_map, bc.local_dst, bc.t2b)


def edge_softmax(scores: jnp.ndarray, dst: jnp.ndarray,
                 num_nodes: int) -> jnp.ndarray:
    """Per-edge stabilized softmax over incoming-edge groups (XLA); ``[E,
    H]`` scores take one softmax per column."""
    return R.edge_softmax_ref(scores, dst, num_nodes)
