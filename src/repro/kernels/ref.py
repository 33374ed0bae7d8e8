"""Pure-jnp oracles for every Pallas kernel (correctness references).

These are deliberately simple (per-row weight gather, compat.segment_*) and
O(E·d·f) regardless of layout — the kernels must match them bit-for-bit in
f32 (tolerance for bf16).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import compat


def segment_mm_ref(x: jnp.ndarray, w: jnp.ndarray, seg_ids: jnp.ndarray,
                   row_scale: jnp.ndarray | None = None) -> jnp.ndarray:
    """y[i] = (row_scale[i] *) x[i] @ w[seg_ids[i]].

    x: [M, k]; w: [R, k, n]; seg_ids: [M] int; row_scale: [M] or None.
    """
    y = jnp.einsum("mk,mkn->mn", x, w[seg_ids])
    if row_scale is not None:
        y = y * row_scale[:, None]
    return y


def gather_mm_ref(feats: jnp.ndarray, w: jnp.ndarray, gather_idx: jnp.ndarray,
                  seg_ids: jnp.ndarray,
                  row_scale: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full GEMM template: Y = (X[G] @ W[T]) with optional per-row scale."""
    return segment_mm_ref(feats[gather_idx], w, seg_ids, row_scale)


def segment_softmax_stats_ref(scores: jnp.ndarray, dst: jnp.ndarray,
                              num_nodes: int):
    """Per-destination max and sum-exp (the stabilized edge-softmax stats)."""
    mx = compat.segment_max(scores, dst, num_nodes)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)  # nodes with no incoming edges
    den = compat.segment_sum(jnp.exp(scores - mx[dst]), dst,
                             num_nodes)
    return mx, den


def edge_softmax_ref(scores: jnp.ndarray, dst: jnp.ndarray, num_nodes: int):
    mx, den = segment_softmax_stats_ref(scores, dst, num_nodes)
    return jnp.exp(scores - mx[dst]) / jnp.maximum(den[dst], 1e-38)


def segment_mm_heads_ref(x: jnp.ndarray, w: jnp.ndarray,
                         seg_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-head typed linear: y[i, h] = x[i, h] @ w[seg_ids[i], h].

    x: [M, H*k]; w: [R, H, k, n] -> [M, H*n]."""
    m, (_, h, k, _) = x.shape[0], w.shape
    y = jnp.einsum("mhk,mhkn->mhn", x.reshape(m, h, k), w[seg_ids])
    return y.reshape(m, -1)


def softmax_agg_ref(scores: jnp.ndarray, msg: jnp.ndarray, dst: jnp.ndarray,
                    num_nodes: int) -> jnp.ndarray:
    """out[v] = sum_{e: dst(e)=v} softmax(scores)_e * msg[e]; with scores
    [E, H] and msg [E, H, d], one softmax per head -> [N, H, d]."""
    att = edge_softmax_ref(scores, dst, num_nodes)
    return compat.segment_sum(att[..., None] * msg, dst, num_nodes)


def weighted_agg_ref(scale: jnp.ndarray | None, msg: jnp.ndarray,
                     dst: jnp.ndarray, num_nodes: int) -> jnp.ndarray:
    """out[v] = sum_{e: dst(e)=v} scale_e * msg[e] (plain traversal agg);
    scale [E, H] weighs msg [E, H, d] per head."""
    contrib = msg if scale is None else scale[..., None] * msg
    return compat.segment_sum(contrib, dst, num_nodes)
