"""RGCN (Schlichtkrull et al., arXiv:1703.06103, Eq. 2) as the program's
model defines it:

    h_v = relu( x_v W_0 + (1 / deg_v) sum_{e = (u -> v, r)} x_u W_r )

Two departures from the paper, both the program's definition: the
normaliser is the destination's in-degree over all relations (DGL's
"right" norm), not c_{v,r} per relation; and the relu is applied in the
last layer too, so the logits are non-negative. Layers are joined by relu.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C


def param_shapes(dims, num_etypes: int, num_ntypes: int):
    """One dict per layer (RGCN has no node-typed weight)."""
    return [{"W_rel": (num_etypes, k, n), "W_self": (k, n)}
            for k, n in zip(dims[:-1], dims[1:])]


def layer(p, x, dg, num_nodes: int, chunk: int, precision: str):
    rp, n_seg = dg["src"].shape[0], num_nodes + 1
    w = C.pad_relations(p["W_rel"], rp)

    def messages(a):
        src, dst, w_c = a
        msg = C.einsum("rmk,rkn->rmn", x[src], w_c, precision)
        return jax.ops.segment_sum(msg.reshape(-1, msg.shape[-1]),
                                   dst.reshape(-1), n_seg)

    agg = C.relation_sum(messages, (dg["src"], dg["dst"], w), chunk,
                         jnp.zeros((n_seg, w.shape[-1]), x.dtype))
    dst = dg["dst"].reshape(-1)
    deg = jax.ops.segment_sum(jnp.ones(dst.shape, jnp.float32), dst, n_seg)
    agg = agg / jnp.maximum(deg, 1.0)[:, None]
    return jnp.maximum(agg[:num_nodes] + C.einsum(
        "nk,kf->nf", x, p["W_self"], precision), 0.0)
