"""Flash attention Pallas kernel (beyond-paper optimization, §Perf v-F).

Motivation from the dry-run roofline: after v-E, qwen3-14b prefill_32k is
memory-bound on the [B,H,Sq,Sk] score materialization (~21 GB/layer/device).
This kernel keeps scores in VMEM with online-softmax accumulation — the
classic flash schedule adapted to TPU: grid over (batch, head, q-tile), K/V
resident in VMEM (S_local · hd · 2B; ≤ 8 MB at the 32k-per-shard sequence
sharding this framework uses), fori_loop over K tiles on the MXU.

Supports: causal masking, sliding windows (Gemma local layers), logit
softcap (Gemma/Grok), GQA (per-head K/V indexing via the h -> h//g block
index map — KV heads are never replicated), q position offset (decode).

Validated in interpret mode against kernels/ref.py (tests/test_flash.py);
compiled path targets real TPU only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kt: int, scale: float,
            causal: bool, window: Optional[int], softcap: Optional[float],
            q_offset: int):
    qt, hd = q_ref.shape[1], q_ref.shape[3]
    s_len = k_ref.shape[1]
    qi = pl.program_id(2)

    q = q_ref[0, :, 0, :].astype(jnp.float32)            # [qt, hd]
    q_pos = q_offset + qi * qt + jax.lax.broadcasted_iota(
        jnp.int32, (qt, kt), 0)

    def body(i, carry):
        acc, m, den = carry
        ks = k_ref[0, pl.ds(i * kt, kt), 0, :].astype(jnp.float32)
        vs = v_ref[0, pl.ds(i * kt, kt), 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [qt, kt]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = i * kt + jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 1)
        mask = jnp.ones((qt, kt), bool)
        if causal:
            mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        den = den * corr + jnp.sum(p, axis=1)
        acc = acc * corr[:, None] + jax.lax.dot(
            p, vs, preferred_element_type=jnp.float32)
        return acc, m_new, den

    acc0 = jnp.zeros((qt, hd), jnp.float32)
    m0 = jnp.full((qt,), _NEG, jnp.float32)
    den0 = jnp.zeros((qt,), jnp.float32)
    acc, m, den = jax.lax.fori_loop(0, s_len // kt, body, (acc0, m0, den0))
    out = acc / jnp.maximum(den, 1e-38)[:, None]
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "q_offset", "q_tile", "k_tile",
    "interpret"))
def flash_attention(
    q: jnp.ndarray,          # [B, Sq, H, hd]
    k: jnp.ndarray,          # [B, Sk, KV, hd]
    v: jnp.ndarray,          # [B, Sk, KV, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    q_tile: int = 128,
    k_tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    q_tile = min(q_tile, sq)
    k_tile = min(k_tile, sk)
    assert sq % q_tile == 0 and sk % k_tile == 0
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _kernel, kt=k_tile, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // q_tile),
        in_specs=[
            pl.BlockSpec((1, q_tile, 1, hd), lambda bi, hi, qi: (bi, qi, hi, 0)),
            pl.BlockSpec((1, sk, 1, hd), lambda bi, hi, qi: (bi, 0, hi // g, 0)),
            pl.BlockSpec((1, sk, 1, hd), lambda bi, hi, qi: (bi, 0, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_tile, 1, hd),
                               lambda bi, hi, qi: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, hd), q.dtype),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
