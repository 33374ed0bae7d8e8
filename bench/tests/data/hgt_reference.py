"""HGT (Hu et al., arXiv:2003.01332) in Hector's single-head form, as a
plain reference (a test fixture: the data-driven test drops it into a
checkout as ``bench/reference/hgt.py``):

    k_v    = x_v W_K[t(v)],  q_v = x_v W_Q[t(v)],  v_v = x_v W_V[t(v)]
    katt_e = k_u W_att[r],   msg_e = v_u W_msg[r]   for e = (u -> v, r)
    a_e    = (katt_e . q_v) / sqrt(n)
    alpha  = softmax of a over the in-edges of each v
    h_v    = sum_e alpha_e msg_e              (0 where v has no in-edge)

Layers are joined by relu. The node-typed linears read ``dg["node_type"]``
and take the number of node types from the weights' leading axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import common as C

NODE_TYPED = ("W_K", "W_Q", "W_V")


def param_shapes(dims, num_etypes: int, num_ntypes: int):
    return [dict({w: (num_ntypes, k, n) for w in NODE_TYPED},
                 W_att=(num_etypes, n, n), W_msg=(num_etypes, n, n))
            for k, n in zip(dims[:-1], dims[1:])]


def _node_typed(x, w, node_type, precision):
    """``x_v w[t(v)]``, one node type at a time."""
    out = jnp.zeros((x.shape[0], w.shape[-1]), x.dtype)
    for t in range(w.shape[0]):
        y = C.einsum("nk,kf->nf", x, w[t], precision)
        out = jnp.where((node_type == t)[:, None], y, out)
    return out


def layer(p, x, dg, num_nodes: int, chunk: int, precision: str):
    rp, n_seg = dg["src"].shape[0], num_nodes + 1
    k, q, v = (_node_typed(x, p[w], dg["node_type"], precision)
               for w in NODE_TYPED)
    wa = C.pad_relations(p["W_att"], rp)
    wm = C.pad_relations(p["W_msg"], rp)
    scale = 1.0 / math.sqrt(wa.shape[-1])

    def scores(a):
        src, dst, wa_c = a
        katt = C.einsum("rmk,rkn->rmn", k[src], wa_c, precision)
        return jnp.sum(katt * q[jnp.minimum(dst, num_nodes - 1)],
                       axis=-1) * scale

    s = C.relation_map(scores, (dg["src"], dg["dst"], wa), chunk)
    alpha = C.segment_softmax(s.reshape(-1), dg["dst"].reshape(-1), n_seg)
    alpha = alpha.reshape(s.shape)

    def aggregate(a):
        src, dst, wm_c, a_c = a
        msg = C.einsum("rmk,rkn->rmn", v[src], wm_c, precision)
        return jax.ops.segment_sum((a_c[..., None] * msg).reshape(
            -1, msg.shape[-1]), dst.reshape(-1), n_seg)

    out = C.relation_sum(aggregate, (dg["src"], dg["dst"], wm, alpha),
                         chunk, jnp.zeros((n_seg, wm.shape[-1]), x.dtype))
    return out[:num_nodes]
