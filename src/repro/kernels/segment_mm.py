"""GEMM-template Pallas kernels (paper Algorithm 1, TPU adaptation).

``segment_mm_padded``  : Y_p = X_p @ W[T[tile]]  (+ fused per-row scale)
``segment_outer_padded``: dW[g] = sum over tiles of g of X_tile^T @ dY_tile
                          (the backward outer-product GEMM instance, §3.5/§4.4)

Both operate on the tile-aligned ``PaddedSegments`` layout (kernels/layout.py):
rows presorted by type, each type segment padded to whole row tiles, and a
scalar-prefetched ``tile_to_group`` map selecting the weight block per tile —
the TPU analogue of the paper's gather/scatter access schemes folded into the
kernel. VMEM blocking:

  X block  (tile_rows, k)      — full reduction dim in VMEM (k ≤ a few K)
  W block  (1, k, tile_n)      — indexed by tile_to_group[i]
  Y block  (tile_rows, tile_n)

MXU alignment: tile_rows defaults to 128 and tile_n to min(n, 128); callers
pick smaller tiles only for tiny test shapes (interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.traversal import gather_rows


def _mm_kernel(t2g_ref, x_ref, w_ref, y_ref):
    acc = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)


def _mm_scale_kernel(t2g_ref, x_ref, w_ref, scale_ref, y_ref):
    acc = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)
    acc = acc * scale_ref[...].astype(jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile_rows", "tile_n", "interpret")
)
def segment_mm_padded(
    x_p: jnp.ndarray,          # [Rp, k]  padded, type-sorted rows
    w: jnp.ndarray,            # [R, k, n]
    t2g: jnp.ndarray,          # [T] int32, non-decreasing tile -> group
    row_scale_p: jnp.ndarray | None = None,   # [Rp, 1] fused epilogue scale
    *,
    tile_rows: int = 128,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    rp, k = x_p.shape
    r, k2, n = w.shape
    assert k == k2, (k, k2)
    assert rp % tile_rows == 0, (rp, tile_rows)
    tile_n = min(tile_n, n)
    assert n % tile_n == 0, (n, tile_n)
    num_tiles = rp // tile_rows
    grid = (num_tiles, n // tile_n)

    in_specs = [
        pl.BlockSpec((tile_rows, k), lambda i, j, t2g: (i, 0)),
        pl.BlockSpec((1, k, tile_n), lambda i, j, t2g: (t2g[i], 0, j)),
    ]
    args = [x_p, w]
    kernel = _mm_kernel
    if row_scale_p is not None:
        in_specs.append(pl.BlockSpec((tile_rows, 1), lambda i, j, t2g: (i, 0)))
        args.append(row_scale_p.reshape(rp, 1))
        kernel = _mm_scale_kernel

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile_rows, tile_n), lambda i, j, t2g: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rp, n), x_p.dtype),
        name="segment_mm_padded",
        interpret=interpret,
    )(t2g, *args)


def _mm_gather_kernel(gidx_ref, t2g_ref, x_ref, w_ref, y_ref, buf_ref):
    xt = gather_rows(gidx_ref, pl.program_id(0) * buf_ref.shape[0], x_ref,
                     buf_ref)
    acc = jnp.dot(xt, w_ref[0], preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)


def _mm_gather_scale_kernel(gidx_ref, t2g_ref, x_ref, w_ref, scale_ref, y_ref,
                            buf_ref):
    xt = gather_rows(gidx_ref, pl.program_id(0) * buf_ref.shape[0], x_ref,
                     buf_ref)
    acc = jnp.dot(xt, w_ref[0], preferred_element_type=jnp.float32)
    acc = acc * scale_ref[...].astype(jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile_rows", "tile_n", "interpret")
)
def segment_mm_gather_padded(
    x: jnp.ndarray,            # [Nx, k]  source rows (node feats / uniques)
    w: jnp.ndarray,            # [R, k, n]
    gidx: jnp.ndarray,         # [Rp] int32 padded slot -> source row, or -1
    t2g: jnp.ndarray,          # [T] int32, non-decreasing tile -> group
    row_scale_p: jnp.ndarray | None = None,   # [Rp, 1] fused epilogue scale
    *,
    tile_rows: int = 128,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather-fused GEMM template: Y_p = X[gidx] @ W[T[tile]].

    Unlike ``segment_mm_padded`` the caller hands over the *ungathered*
    source tensor; the per-row gather runs inside the kernel from the
    scalar-prefetched (SMEM) index layout, so no ``[Rp, k]`` (edge-wide)
    input copy is ever materialized in HBM. The source block stays resident
    in VMEM across grid steps (its index_map is constant).
    """
    nx, k = x.shape
    r, k2, n = w.shape
    assert k == k2, (k, k2)
    (rp,) = gidx.shape
    assert rp % tile_rows == 0, (rp, tile_rows)
    tile_n = min(tile_n, n)
    assert n % tile_n == 0, (n, tile_n)
    num_tiles = rp // tile_rows
    grid = (num_tiles, n // tile_n)

    in_specs = [
        pl.BlockSpec((nx, k), lambda i, j, gidx, t2g: (0, 0)),
        pl.BlockSpec((1, k, tile_n), lambda i, j, gidx, t2g: (t2g[i], 0, j)),
    ]
    args = [x, w]
    kernel = _mm_gather_kernel
    if row_scale_p is not None:
        in_specs.append(
            pl.BlockSpec((tile_rows, 1), lambda i, j, gidx, t2g: (i, 0)))
        args.append(row_scale_p.reshape(rp, 1))
        kernel = _mm_gather_scale_kernel

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile_rows, tile_n),
                                   lambda i, j, gidx, t2g: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_rows, k), x.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((rp, n), x.dtype),
        name="segment_mm_gather_padded",
        interpret=interpret,
    )(gidx, t2g, *args)


def _outer_kernel(meta_ref, x_ref, dy_ref, dw_ref):
    """Accumulating outer product; meta_ref[0] = t2g, meta_ref[1] = is_first."""
    t = pl.program_id(0)
    is_first = meta_ref[1, t]

    @pl.when(is_first == 1)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    acc = jax.lax.dot_general(
        x_ref[...], dy_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dw_ref[...] += acc[None].astype(dw_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_groups", "tile_rows", "interpret"))
def segment_outer_padded(
    x_p: jnp.ndarray,          # [Rp, k]
    dy_p: jnp.ndarray,         # [Rp, n]
    t2g: jnp.ndarray,          # [T] int32 non-decreasing
    *,
    num_groups: int,
    tile_rows: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """dW[g] = sum_{tiles t of group g} x_t^T @ dy_t  -> [R, k, n] (f32)."""
    rp, k = x_p.shape
    rp2, n = dy_p.shape
    assert rp == rp2
    assert rp % tile_rows == 0
    num_tiles = rp // tile_rows
    # is_first[t] = 1 iff t is the first tile of its group
    prev = jnp.concatenate([jnp.array([-1], jnp.int32), t2g[:-1]])
    is_first = (t2g != prev).astype(jnp.int32)
    meta = jnp.stack([t2g.astype(jnp.int32), is_first])  # [2, T]

    return pl.pallas_call(
        _outer_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[
                pl.BlockSpec((tile_rows, k), lambda t, meta: (t, 0)),
                pl.BlockSpec((tile_rows, n), lambda t, meta: (t, 0)),
            ],
            out_specs=pl.BlockSpec((1, k, n), lambda t, meta: (meta[0, t], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), jnp.float32),
        name="segment_outer_padded",
        interpret=interpret,
    )(meta, x_p, dy_p)
