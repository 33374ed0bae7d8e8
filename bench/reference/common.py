"""Shared pieces of the plain references: precision-explicit matmuls,
relation-blocked typed linears, edge softmax, cross-entropy and AdamW.

Plain ``jax.numpy`` in float32. Nothing here imports the program: the
references take the benchmark's own graph arrays, weights and features.

``precision`` selects how every matmul of a reference runs:

* ``"highest"``: float32 at ``lax.Precision.HIGHEST`` (the configurations'
  precision);
* ``"high"``: three bfloat16 passes. This is the control: the nearest
  precision below "highest";
* ``"bfloat16"``: one bfloat16 pass.

On a TPU these are the chip's own ``Precision.HIGH`` and ``DEFAULT``. A
CPU computes float32 whatever the precision asked, so there the passes are
written out: the operands split into bfloat16 halves, hi*hi + hi*lo +
lo*hi for "high", hi*hi for "bfloat16".
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high", "bfloat16")
_HI = jax.lax.Precision.HIGHEST
_NATIVE = {"high": jax.lax.Precision.HIGH,
           "bfloat16": jax.lax.Precision.DEFAULT}


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def einsum(subscripts: str, a, b, precision: str = "highest"):
    """``jnp.einsum`` of two float32 operands at ``precision``."""
    if precision == "highest":
        return jnp.einsum(subscripts, a, b, precision=_HI)
    if jax.default_backend() == "tpu" and precision in _NATIVE:
        return jnp.einsum(subscripts, a, b, precision=_NATIVE[precision])
    if precision == "high":
        a_hi, b_hi = _bf16(a), _bf16(b)
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return (jnp.einsum(subscripts, a_hi, b_hi, precision=_HI)
                + jnp.einsum(subscripts, a_hi, b_lo, precision=_HI)
                + jnp.einsum(subscripts, a_lo, b_hi, precision=_HI))
    if precision == "bfloat16":
        return jnp.einsum(subscripts, _bf16(a), _bf16(b), precision=_HI)
    raise ValueError(f"precision {precision!r}; pick one of {PRECISIONS}")


@dataclasses.dataclass(frozen=True)
class EdgeGraph:
    """A graph as the references read it: the edges grouped by relation
    into equal-length blocks (host arrays, built once).

    ``src[r, m]`` / ``dst[r, m]`` are the ends of relation ``r``'s ``m``-th
    edge. Pad slots have ``dst == num_nodes``, a dummy segment that no real
    node reads; relations are padded with empty blocks to a multiple of
    ``chunk``, the number of relations a reference computes at once.
    ``node_type[v]`` is node ``v``'s type, where the graph has them."""

    src: np.ndarray          # [Rp, M] int32
    dst: np.ndarray          # [Rp, M] int32
    num_nodes: int
    num_etypes: int
    chunk: int
    node_type: Optional[np.ndarray] = None    # [num_nodes] int32


# largest [chunk, M, width] float32 intermediate a reference makes at once
CHUNK_BYTES = 1 << 29


def edge_graph(src, dst, etype, num_nodes: int, num_etypes: int,
               block: int = 1, width: int = 64,
               node_type=None) -> EdgeGraph:
    """Group the edges by relation (block length rounded up to a multiple
    of ``block``); ``width`` sizes the relation chunks; ``node_type`` is
    kept as it is."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    etype = np.asarray(etype, np.int32)
    order = np.argsort(etype, kind="stable")
    counts = np.bincount(etype, minlength=num_etypes)
    m = max(1, int(counts.max()) if counts.size else 1)
    m = -(-m // block) * block
    chunk = max(1, min(num_etypes, CHUNK_BYTES // (4 * m * width)))
    rp = -(-num_etypes // chunk) * chunk
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(etype.size) - np.repeat(starts, counts)
    s = np.zeros((rp, m), np.int32)
    d = np.full((rp, m), num_nodes, np.int32)
    s[etype[order], slot] = src[order]
    d[etype[order], slot] = dst[order]
    if node_type is not None:
        node_type = np.asarray(node_type, np.int32)
    return EdgeGraph(s, d, int(num_nodes), int(num_etypes), chunk,
                     node_type)


def device_graph(g: EdgeGraph) -> Dict[str, jnp.ndarray]:
    """What a model's ``layer`` reads: ``src`` and ``dst`` blocks, and
    ``node_type`` where the graph has them."""
    dg = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst)}
    if g.node_type is not None:
        dg["node_type"] = jnp.asarray(g.node_type)
    return dg


def pad_relations(w, rp: int):
    """Per-relation weights padded with zeros to ``rp`` relations."""
    return jnp.pad(w, [(0, rp - w.shape[0])] + [(0, 0)] * (w.ndim - 1))


def _chunked(xs, chunk: int):
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] // chunk, chunk) + a.shape[1:]), xs)


def relation_map(fn, xs, chunk: int):
    """``fn`` over ``chunk`` relations at a time (recomputed in the
    backward pass), outputs stacked back to ``[Rp, ...]``."""
    out = jax.lax.map(jax.checkpoint(fn), _chunked(xs, chunk))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


def relation_sum(fn, xs, chunk: int, init):
    """The sum of ``fn`` over chunks of relations (recomputed in the
    backward pass)."""
    body = jax.checkpoint(fn)

    def step(acc, x):
        return acc + body(x), None
    return jax.lax.scan(step, init, _chunked(xs, chunk))[0]


def segment_softmax(scores, dst, num_segments: int):
    """Softmax of edge scores over each destination's in-edges."""
    mx = jax.ops.segment_max(scores, dst, num_segments)
    mx = jax.lax.stop_gradient(jnp.where(jnp.isfinite(mx), mx, 0.0))
    ex = jnp.exp(scores - mx[dst])
    den = jax.ops.segment_sum(ex, dst, num_segments)
    return ex / den[dst]


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    """AdamW with decoupled weight decay (Loshchilov & Hutter,
    arXiv:1711.05101) after global-norm clipping of the gradient."""

    learning_rate: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float

    @staticmethod
    def from_config(d: dict) -> "AdamWConfig":
        return AdamWConfig(**{f.name: float(d[f.name])
                              for f in dataclasses.fields(AdamWConfig)})


def clip_by_global_norm(grads, clip_norm: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(params, grads, mu, nu, step: int, hp: AdamWConfig):
    """One AdamW update at 1-based ``step``; returns
    ``(params, mu, nu, clipped grads)``."""
    g = clip_by_global_norm(grads, hp.clip_norm)
    mu = jax.tree.map(lambda m, x: hp.b1 * m + (1 - hp.b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: hp.b2 * v + (1 - hp.b2) * x * x, nu, g)
    bc1 = 1.0 - hp.b1 ** step
    bc2 = 1.0 - hp.b2 ** step

    def upd(p, m, v):
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + hp.eps)
        return p - hp.learning_rate * (delta + hp.weight_decay * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu, g
