"""HGT as published (Hu, Dong, Wang and Sun, WWW 2020, arXiv:2003.01332,
Eqs. 3-5; pyHGT's ``HGTConv``), with H heads of dk = d / H. For edge
e = (u -> v) of relation r, node types t(.), and each head h:

    k_u = x_u W_K[t(u)],  q_v = x_v W_Q[t(v)],  m_u = x_u W_V[t(u)]
    a_e^h   = mu[r, h] (k_u^h W_att[r, h]) . q_v^h / sqrt(dk)
    alpha^h = softmax of a^h over the in-edges of each v
    agg_v   = concat_h sum_e alpha_e^h m_u^h W_msg[r, h]   (0 without in-edges)
    o_v     = gelu(agg_v) W_A[t(v)]                        (exact GELU)
    h_v     = s o_v + (1 - s) x_v,  s = sigmoid(w_skip[t(v)]),
              where the layer's input and output widths are equal

Departures from pyHGT, each deliberate:

* no dropout;
* no per-type LayerNorm (pyHGT's ``use_norm``, which Eq. 5 does not have);
* no input adapt layer: the features are drawn at the model's width;
* layers are joined by relu, the benchmark stack's join;
* the last layer's A-Linear is the output head (2 classes), in place of
  pyHGT's separate classifier; its widths differ, so it has no skip;
* full-graph steps in place of HGSampling's sampled subgraphs.

Plain ``jax.numpy`` in float32 at the caller's precision (``C.einsum``).
Node-typed linears go one node type at a time; edge tensors stay in the
relation blocks of ``common.EdgeGraph``, a chunk of relations at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import common as C

HEADS = 8                   # the paper's 8 heads (§4.2)
NODE_TYPED = ("W_K", "W_Q", "W_V")
# the relation chunk the caller sizes is for 64-wide edge tensors
CHUNK_WIDTH = 64


def param_shapes(dims, num_etypes: int, num_ntypes: int,
                 heads: int = HEADS):
    """The parameter pytree the model takes, one dict per layer."""
    out = []
    for k, n in zip(dims[:-1], dims[1:]):
        dk = k // heads
        p = {w: (num_ntypes, k, k) for w in NODE_TYPED}
        p.update(W_att=(num_etypes, heads, dk, dk),
                 W_msg=(num_etypes, heads, dk, dk),
                 w_prior=(num_etypes, heads), W_A=(num_ntypes, k, n))
        if k == n:
            p["w_skip"] = (num_ntypes,)
        out.append(p)
    return out


def _node_typed(x, w, node_type, precision):
    """``x_v w[t(v)]``, one node type at a time (a loop over the types)."""
    def one_type(out, a):
        t, w_t = a
        y = C.einsum("nk,kf->nf", x, w_t, precision)
        return jnp.where((node_type == t)[:, None], y, out), None

    out = jnp.zeros((x.shape[0], w.shape[-1]), x.dtype)
    types = jnp.arange(w.shape[0], dtype=node_type.dtype)
    return jax.lax.scan(jax.checkpoint(one_type), out, (types, w))[0]


def _sub_chunk(chunk: int, width: int) -> int:
    """The largest divisor of ``chunk`` that keeps a ``width``-wide chunk
    of relations within the bytes a 64-wide one of ``chunk`` takes."""
    cap = max(1, chunk * CHUNK_WIDTH // width)
    return max(c for c in range(1, chunk + 1) if chunk % c == 0 and c <= cap)


def layer(p, x, dg, num_nodes: int, chunk: int, precision: str):
    rp, n_seg = dg["src"].shape[0], num_nodes + 1
    node_type = dg["node_type"]
    h, dk = p["W_att"].shape[1], p["W_att"].shape[2]
    k, q, v = (_node_typed(x, p[w], node_type, precision)
               for w in NODE_TYPED)
    wa = C.pad_relations(p["W_att"], rp)
    wm = C.pad_relations(p["W_msg"], rp)
    pri = C.pad_relations(p["w_prior"], rp)
    chunk = _sub_chunk(chunk, h * dk)

    def heads(a, rows):
        return a[rows].reshape(rows.shape + (h, dk))

    def scores(a):
        src, dst, wa_c, pri_c = a
        katt = C.einsum("rmhk,rhkn->rmhn", heads(k, src), wa_c, precision)
        qd = heads(q, jnp.minimum(dst, num_nodes - 1))
        return (jnp.sum(katt * qd, axis=-1) * pri_c[:, None, :]
                / math.sqrt(dk))

    s = C.relation_map(scores, (dg["src"], dg["dst"], wa, pri), chunk)
    alpha = C.segment_softmax(s.reshape(-1, h), dg["dst"].reshape(-1),
                              n_seg).reshape(s.shape)

    def aggregate(a):
        src, dst, wm_c, a_c = a
        msg = C.einsum("rmhk,rhkn->rmhn", heads(v, src), wm_c, precision)
        return jax.ops.segment_sum(
            (a_c[..., None] * msg).reshape(-1, h * dk), dst.reshape(-1),
            n_seg)

    agg = C.relation_sum(aggregate, (dg["src"], dg["dst"], wm, alpha),
                         chunk, jnp.zeros((n_seg, h * dk), x.dtype))
    o = _node_typed(jax.nn.gelu(agg[:num_nodes], approximate=False),
                    p["W_A"], node_type, precision)
    if "w_skip" not in p:
        return o
    gate = jax.nn.sigmoid(p["w_skip"])[node_type][:, None]
    return gate * o + (1.0 - gate) * x
