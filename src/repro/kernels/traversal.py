"""Traversal-template Pallas kernels (paper Algorithm 2, TPU adaptation).

GPU Hector aggregates into destination rows with atomics (and identifies the
resulting latency bound in §4.4). TPU Pallas grids are sequential per core,
so we instead use the ``BlockedCSR`` layout (kernels/layout.py): edges sorted
by destination, padded so each edge tile belongs to one destination-node
block, and consecutive edge tiles of a block **accumulate into the same VMEM
output block** (deterministic, contention-free).

Kernels (all derived traversal-template instances):

``seg_stats_padded``        per-destination (max, sum-exp) in ONE pass using
                            online-softmax rescaling — the paper's
                            "partial result aggregation" adapted to TPU.
``seg_softmax_agg_padded``  out[v] = Σ_e softmax(score)_e · msg_e
                            (fused edge-softmax + weighted aggregation: the
                            canonical fused traversal region of Listing 1).
``seg_weighted_agg_padded`` out[v] = Σ_e scale_e · msg_e (RGCN-style).
``seg_row_sum_padded``      out[:, i] = Σ_{e reads row i} g[:, e] — the
                            backward of the compact message gather
                            ``msg[msg_rows]``, over edges sorted by message
                            row (``layout.msg_rows_csr``).

The scatter maps onto the MXU: each tile builds a ``[node_block, tile]``
one-hot of its edges' destinations, scales it by the per-edge weight, and
contracts it with the tile's messages. The same one-hot also reads each
edge's destination statistics (a masked column reduction), so no kernel
indexes a vector by a vector.

Block shapes follow the TPU rule that a block's last two dims divide by
(8, 128) or equal the array's: per-tile scalars travel as ``[T, 1, tile]``
with the tile dim squeezed, and per-node statistics as ``[NBk * NB, 1]``
columns, one ``(node_block, 1)`` block per destination-node block.

``*_gather_padded`` variants additionally fold the message gather into the
kernel: instead of materializing the padded dst-sorted ``[Ep, d]`` message
copy in HBM before the call, the caller passes messages in their storage
order (canonical edge order, or the compact unique-pair table) plus a
scalar-prefetched padded row-index map (slot -> message row, -1 for pads);
each grid step copies its tile row by row from the VMEM-resident message
block — the paper's in-kernel gather access scheme applied to the traversal
template. The map lives in SMEM, so its size is bounded by SMEM, not VMEM
(see ``tune/device.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def gather_rows(map_ref, base, src_ref, buf_ref):
    """``buf[r] = src[map[base + r]]`` for every row of ``buf``; rows whose
    map entry is -1 become zeros. The map is read as SMEM scalars and each
    source row as a one-row VMEM slice — the TPU compiler accepts neither a
    vector load from SMEM nor a vector-indexed gather from VMEM."""

    def body(r, carry):
        row = map_ref[base + r]
        v = src_ref[pl.ds(jnp.maximum(row, 0), 1), :].astype(buf_ref.dtype)
        buf_ref[pl.ds(r, 1), :] = jnp.where(row >= 0, v, jnp.zeros_like(v))
        return carry

    jax.lax.fori_loop(0, buf_ref.shape[0], body, 0)
    return buf_ref[...]


def _block_meta(t2b: jnp.ndarray) -> jnp.ndarray:
    """``[2, T]`` scalar-prefetch table: row 0 the tile -> node-block map,
    row 1 whether the tile is its block's first (which zeroes the block)."""
    prev = jnp.concatenate([jnp.array([-1], jnp.int32), t2b[:-1]])
    return jnp.stack([t2b.astype(jnp.int32), (t2b != prev).astype(jnp.int32)])


def _tile_spec(tile):
    """One edge tile's row of a ``[T, 1, tile]`` per-edge scalar array."""
    return pl.BlockSpec((None, 1, tile), lambda t, *pref: (t, 0, 0))


def _node_spec(node_block, cols):
    """The destination-node block a tile accumulates into (``meta`` is the
    last scalar-prefetch operand)."""
    return pl.BlockSpec((node_block, cols),
                        lambda t, *pref: (pref[-1][0, t], 0))


def _first_tile(meta_ref):
    return meta_ref[1, pl.program_id(0)] == 1


def _onehot(dst_ref, node_block):
    """``[node_block, tile]`` mask of each edge's local destination row;
    pad edges (dst == node_block) match no row."""
    dst = dst_ref[...]                                   # [1, tile]
    rows = jax.lax.broadcasted_iota(jnp.int32, (node_block, dst.shape[-1]), 0)
    return rows == dst


def _per_edge(mask, col):
    """Each edge's value of a per-node column ``[node_block, 1]`` -> [1, tile]
    (zeros for pad edges)."""
    return jnp.sum(jnp.where(mask, col, 0.0), axis=0, keepdims=True)


def _softmax_weights(mask, scores_ref, mx_ref, den_ref):
    """One-hot scaled by each edge's softmax weight -> [node_block, tile]."""
    s = scores_ref[...].astype(jnp.float32)              # [1, tile]
    att = jnp.exp(s - _per_edge(mask, mx_ref[...])) / jnp.maximum(
        _per_edge(mask, den_ref[...]), 1e-38)
    return jnp.where(mask, att, 0.0)


def _accumulate(meta_ref, out_ref, weights, msg):
    @pl.when(_first_tile(meta_ref))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    contrib = jax.lax.dot(weights, msg.astype(jnp.float32),
                          preferred_element_type=jnp.float32)  # [NB, d]
    out_ref[...] += contrib.astype(out_ref.dtype)


def _stats_kernel(meta_ref, scores_ref, dst_ref, mx_ref, den_ref, *, node_block):
    @pl.when(_first_tile(meta_ref))
    def _init():
        mx_ref[...] = jnp.full_like(mx_ref, _NEG_INF)
        den_ref[...] = jnp.zeros_like(den_ref)

    mask = _onehot(dst_ref, node_block)                  # [NB, tile]
    masked = jnp.where(mask, scores_ref[...].astype(jnp.float32), _NEG_INF)
    m_old = mx_ref[...]                                  # [NB, 1]
    m_new = jnp.maximum(m_old, jnp.max(masked, axis=1, keepdims=True))
    # online rescale; guard -inf - -inf
    old_factor = jnp.where(m_old <= _NEG_INF, 0.0, jnp.exp(m_old - m_new))
    t_den = jnp.sum(jnp.where(mask, jnp.exp(masked - m_new), 0.0), axis=1,
                    keepdims=True)
    mx_ref[...] = m_new
    den_ref[...] = den_ref[...] * old_factor + t_den


@functools.partial(
    jax.jit, static_argnames=("node_block", "num_node_blocks", "interpret")
)
def seg_stats_padded(
    scores_p: jnp.ndarray,     # [T, tile] dst-sorted padded scores (pads: any)
    local_dst_p: jnp.ndarray,  # [T, tile] int32 local dst (pads: node_block)
    t2b: jnp.ndarray,          # [T] int32 non-decreasing tile -> node block
    *,
    node_block: int,
    num_node_blocks: int,
    interpret: bool = False,
):
    """-> (max, sum-exp), each ``[num_node_blocks * node_block, 1]``."""
    num_tiles, tile = scores_p.shape
    stat = jax.ShapeDtypeStruct((num_node_blocks * node_block, 1),
                                jnp.float32)
    return pl.pallas_call(
        functools.partial(_stats_kernel, node_block=node_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[_tile_spec(tile), _tile_spec(tile)],
            out_specs=[_node_spec(node_block, 1), _node_spec(node_block, 1)],
        ),
        out_shape=[stat, stat],
        name="seg_stats_padded",
        interpret=interpret,
    )(_block_meta(t2b), scores_p.reshape(num_tiles, 1, tile),
      local_dst_p.reshape(num_tiles, 1, tile))


def _softmax_agg_kernel(meta_ref, scores_ref, dst_ref, msg_ref, mx_ref,
                        den_ref, out_ref, *, node_block):
    mask = _onehot(dst_ref, node_block)
    _accumulate(meta_ref, out_ref,
                _softmax_weights(mask, scores_ref, mx_ref, den_ref),
                msg_ref[...])


@functools.partial(
    jax.jit, static_argnames=("node_block", "num_node_blocks", "interpret")
)
def seg_softmax_agg_padded(
    scores_p: jnp.ndarray,     # [T, tile]
    msg_p: jnp.ndarray,        # [T*tile, d]  dst-sorted padded messages
    local_dst_p: jnp.ndarray,  # [T, tile]
    t2b: jnp.ndarray,          # [T]
    mx: jnp.ndarray,           # [NBk*NB, 1]  from seg_stats_padded
    den: jnp.ndarray,          # [NBk*NB, 1]
    *,
    node_block: int,
    num_node_blocks: int,
    interpret: bool = False,
) -> jnp.ndarray:
    num_tiles, tile = scores_p.shape
    d = msg_p.shape[-1]
    return pl.pallas_call(
        functools.partial(_softmax_agg_kernel, node_block=node_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[
                _tile_spec(tile),
                _tile_spec(tile),
                pl.BlockSpec((tile, d), lambda t, meta: (t, 0)),
                _node_spec(node_block, 1),
                _node_spec(node_block, 1),
            ],
            out_specs=_node_spec(node_block, d),
        ),
        out_shape=jax.ShapeDtypeStruct((num_node_blocks * node_block, d),
                                       msg_p.dtype),
        name="seg_softmax_agg_padded",
        interpret=interpret,
    )(_block_meta(t2b), scores_p.reshape(num_tiles, 1, tile),
      local_dst_p.reshape(num_tiles, 1, tile), msg_p, mx, den)


def _softmax_agg_gather_kernel(mmap_ref, meta_ref, scores_ref, dst_ref,
                               msg_ref, mx_ref, den_ref, out_ref, buf_ref, *,
                               node_block):
    tile = buf_ref.shape[0]
    msg = gather_rows(mmap_ref, pl.program_id(0) * tile, msg_ref, buf_ref)
    mask = _onehot(dst_ref, node_block)
    _accumulate(meta_ref, out_ref,
                _softmax_weights(mask, scores_ref, mx_ref, den_ref), msg)


@functools.partial(
    jax.jit, static_argnames=("node_block", "num_node_blocks", "interpret")
)
def seg_softmax_agg_gather_padded(
    scores_p: jnp.ndarray,     # [T, tile] dst-sorted padded scores
    msg: jnp.ndarray,          # [Em, d]  messages in storage order
    mmap: jnp.ndarray,         # [T*tile] int32 slot -> message row, or -1
    local_dst_p: jnp.ndarray,  # [T, tile]
    t2b: jnp.ndarray,          # [T]
    mx: jnp.ndarray,           # [NBk*NB, 1]  from seg_stats_padded
    den: jnp.ndarray,          # [NBk*NB, 1]
    *,
    node_block: int,
    num_node_blocks: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather-fused fused-softmax aggregation: messages are gathered inside
    the kernel from their storage-order block (canonical edges or the
    compact unique table), never materialized per padded slot in HBM."""
    num_tiles, tile = scores_p.shape
    em, d = msg.shape
    return pl.pallas_call(
        functools.partial(_softmax_agg_gather_kernel, node_block=node_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(num_tiles,),
            in_specs=[
                _tile_spec(tile),
                _tile_spec(tile),
                pl.BlockSpec((em, d), lambda t, mmap, meta: (0, 0)),
                _node_spec(node_block, 1),
                _node_spec(node_block, 1),
            ],
            out_specs=_node_spec(node_block, d),
            scratch_shapes=[pltpu.VMEM((tile, d), msg.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_node_blocks * node_block, d),
                                       msg.dtype),
        name="seg_softmax_agg_gather_padded",
        interpret=interpret,
    )(mmap, _block_meta(t2b), scores_p.reshape(num_tiles, 1, tile),
      local_dst_p.reshape(num_tiles, 1, tile), msg, mx, den)


def _weighted_agg_gather_kernel(mmap_ref, meta_ref, scale_ref, dst_ref,
                                msg_ref, out_ref, buf_ref, *, node_block):
    tile = buf_ref.shape[0]
    msg = gather_rows(mmap_ref, pl.program_id(0) * tile, msg_ref, buf_ref)
    mask = _onehot(dst_ref, node_block)
    weights = jnp.where(mask, scale_ref[...].astype(jnp.float32), 0.0)
    _accumulate(meta_ref, out_ref, weights, msg)


@functools.partial(
    jax.jit, static_argnames=("node_block", "num_node_blocks", "interpret")
)
def seg_weighted_agg_gather_padded(
    scale_p: jnp.ndarray,      # [T, tile] per-edge scalar (pads: 0)
    msg: jnp.ndarray,          # [Em, d]  messages in storage order
    mmap: jnp.ndarray,         # [T*tile] int32 slot -> message row, or -1
    local_dst_p: jnp.ndarray,  # [T, tile]
    t2b: jnp.ndarray,          # [T]
    *,
    node_block: int,
    num_node_blocks: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather-fused weighted aggregation (RGCN-style sum/mean numerator)."""
    num_tiles, tile = scale_p.shape
    em, d = msg.shape
    return pl.pallas_call(
        functools.partial(_weighted_agg_gather_kernel, node_block=node_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(num_tiles,),
            in_specs=[
                _tile_spec(tile),
                _tile_spec(tile),
                pl.BlockSpec((em, d), lambda t, mmap, meta: (0, 0)),
            ],
            out_specs=_node_spec(node_block, d),
            scratch_shapes=[pltpu.VMEM((tile, d), msg.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_node_blocks * node_block, d),
                                       msg.dtype),
        name="seg_weighted_agg_gather_padded",
        interpret=interpret,
    )(mmap, _block_meta(t2b), scale_p.reshape(num_tiles, 1, tile),
      local_dst_p.reshape(num_tiles, 1, tile), msg)


def _weighted_agg_kernel(meta_ref, scale_ref, dst_ref, msg_ref, out_ref, *,
                         node_block):
    mask = _onehot(dst_ref, node_block)
    weights = jnp.where(mask, scale_ref[...].astype(jnp.float32), 0.0)
    _accumulate(meta_ref, out_ref, weights, msg_ref[...])


@functools.partial(
    jax.jit, static_argnames=("node_block", "num_node_blocks", "interpret")
)
def seg_weighted_agg_padded(
    scale_p: jnp.ndarray,      # [T, tile] per-edge scalar (pads: 0); ones for plain sum
    msg_p: jnp.ndarray,        # [T*tile, d]
    local_dst_p: jnp.ndarray,  # [T, tile]
    t2b: jnp.ndarray,          # [T]
    *,
    node_block: int,
    num_node_blocks: int,
    interpret: bool = False,
) -> jnp.ndarray:
    num_tiles, tile = scale_p.shape
    d = msg_p.shape[-1]
    return pl.pallas_call(
        functools.partial(_weighted_agg_kernel, node_block=node_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[
                _tile_spec(tile),
                _tile_spec(tile),
                pl.BlockSpec((tile, d), lambda t, meta: (t, 0)),
            ],
            out_specs=_node_spec(node_block, d),
        ),
        out_shape=jax.ShapeDtypeStruct((num_node_blocks * node_block, d),
                                       msg_p.dtype),
        name="seg_weighted_agg_padded",
        interpret=interpret,
    )(_block_meta(t2b), scale_p.reshape(num_tiles, 1, tile),
      local_dst_p.reshape(num_tiles, 1, tile), msg_p)


def _row_sum_kernel(meta_ref, row_ref, g_ref, out_ref, *, row_block):
    @pl.when(_first_tile(meta_ref))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    onehot = _onehot(row_ref, row_block).astype(jnp.float32)  # [RB, tile]
    # exact products of 0/1 and float32 contributions, summed in float32
    out_ref[...] += jax.lax.dot_general(
        g_ref[...].astype(jnp.float32), onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("row_block", "num_row_blocks", "interpret")
)
def seg_row_sum_padded(
    g_p: jnp.ndarray,          # [d, T*tile] per-slot rows, edges on lanes
    local_row_p: jnp.ndarray,  # [T, tile] int32 row - block start (pads: row_block)
    t2b: jnp.ndarray,          # [T] int32 non-decreasing tile -> row block
    *,
    row_block: int,
    num_row_blocks: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sum slot columns by message row -> ``[d, num_row_blocks * row_block]``.

    Slots are sorted by row and padded so a tile holds one row block's
    edges; each tile contracts its ``[d, tile]`` columns with the one-hot of
    their rows into the row block's VMEM output, as the aggregations sum
    into destination blocks. Edges sit on the lane axis, so a narrow ``d``
    (11, 32) is not padded to 128 lanes in HBM."""
    num_tiles, tile = local_row_p.shape
    d = g_p.shape[0]
    return pl.pallas_call(
        functools.partial(_row_sum_kernel, row_block=row_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[
                _tile_spec(tile),
                pl.BlockSpec((d, tile), lambda t, meta: (0, t)),
            ],
            out_specs=pl.BlockSpec((d, row_block),
                                   lambda t, meta: (0, meta[0, t])),
        ),
        out_shape=jax.ShapeDtypeStruct((d, num_row_blocks * row_block),
                                       g_p.dtype),
        name="seg_row_sum_padded",
        interpret=interpret,
    )(_block_meta(t2b), local_row_p.reshape(num_tiles, 1, tile), g_p)
