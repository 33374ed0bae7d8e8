"""RGAT (Busbridge et al., arXiv:1904.05811, as Hector's Listing 1 writes
it), single head:

    hs_e   = x_u W_r                      for edge e = (u -> v, relation r)
    a_e    = leaky_relu(hs_e . w_s[r] + (x_v W_r) . w_t[r], 0.01)
    alpha  = softmax of a over the in-edges of each v
    h_v    = sum_e alpha_e hs_e           (0 where v has no in-edge)

Layers are joined by relu; the last layer's output is the logits. Edge
tensors stay in the relation blocks of ``common.EdgeGraph``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C

SLOPE = 0.01


def param_shapes(dims, num_etypes: int, num_ntypes: int):
    """The parameter pytree the model takes, one dict per layer (RGAT has
    no node-typed weight)."""
    return [{"W_rel": (num_etypes, k, n), "w_att_src": (num_etypes, n),
             "w_att_dst": (num_etypes, n)}
            for k, n in zip(dims[:-1], dims[1:])]


def layer(p, x, dg, num_nodes: int, chunk: int, precision: str):
    rp, n_seg = dg["src"].shape[0], num_nodes + 1
    w = C.pad_relations(p["W_rel"], rp)
    ws = C.pad_relations(p["w_att_src"], rp)
    wt = C.pad_relations(p["w_att_dst"], rp)

    def scores(a):
        src, dst, w_c, ws_c, wt_c = a
        hs = C.einsum("rmk,rkn->rmn", x[src], w_c, precision)
        hd = C.einsum("rmk,rkn->rmn", x[jnp.minimum(dst, num_nodes - 1)], w_c, precision)
        return (C.einsum("rmn,rn->rm", hs, ws_c, precision)
                + C.einsum("rmn,rn->rm", hd, wt_c, precision))

    s = C.relation_map(scores, (dg["src"], dg["dst"], w, ws, wt), chunk)
    s = jnp.where(s > 0, s, SLOPE * s)
    alpha = C.segment_softmax(s.reshape(-1), dg["dst"].reshape(-1), n_seg)
    alpha = alpha.reshape(s.shape)

    def aggregate(a):
        src, dst, w_c, a_c = a
        hs = C.einsum("rmk,rkn->rmn", x[src], w_c, precision)
        return jax.ops.segment_sum((a_c[..., None] * hs).reshape(
            -1, hs.shape[-1]), dst.reshape(-1), n_seg)

    out = C.relation_sum(aggregate, (dg["src"], dg["dst"], w, alpha),
                         chunk, jnp.zeros((n_seg, w.shape[-1]), x.dtype))
    return out[:num_nodes]
