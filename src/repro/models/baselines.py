"""Vanilla baseline implementations (the systems Hector is compared against).

These reproduce the inefficiencies the paper profiles in §2.3 / Fig. 4, and
are the tests' reference for the generated code **with identical numerics**
(same parameter pytrees as the HectorModule plans):

* ``typed_linear_replicated``  — materializes the [E, d_in, d_out] per-edge
  weight tensor (PyG FastRGCNConv / bmm pattern): the "huge temporary weight
  tensor" of §2.3.
* ``typed_linear_per_type_loop`` — one dense GEMM *per relation* with masked
  scatter (DGL HeteroConv python-loop pattern; serialized small kernels).
* full vanilla RGCN / RGAT / HGT forwards built from those pieces
  (vanilla materialization everywhere, no reordering, no compaction).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro import compat
from repro.core.graph import GraphTensors
from repro.kernels import ref as R


def typed_linear_replicated(x: jnp.ndarray, w: jnp.ndarray,
                            types: jnp.ndarray) -> jnp.ndarray:
    """bmm with replicated weights: W'[i] = W[T[i]] (the §2.3 anti-pattern)."""
    w_rep = w[types]                       # [M, d_in, d_out]  (materialized!)
    return jnp.einsum("mk,mkn->mn", x, w_rep)


def typed_linear_per_type_loop(x: jnp.ndarray, w: jnp.ndarray,
                               types: jnp.ndarray) -> jnp.ndarray:
    """Per-relation GEMM + mask (serialized small kernels)."""
    out = jnp.zeros((x.shape[0], w.shape[-1]), x.dtype)
    for r in range(w.shape[0]):  # python loop == serial kernel launches
        mask = (types == r)[:, None]
        out = out + jnp.where(mask, x @ w[r], 0.0)
    return out


def _maybe_loop(x, w, types, per_type_loop: bool):
    if per_type_loop:
        return typed_linear_per_type_loop(x, w, types)
    return typed_linear_replicated(x, w, types)


# ---------------------------------------------------------------------------
# full vanilla model forwards (match HectorModule numerics)
# ---------------------------------------------------------------------------
def rgcn_vanilla(params: Dict, gt: GraphTensors, feats: Dict,
                 activation: str = "relu", per_type_loop: bool = False):
    x = feats["feature"]
    msg = _maybe_loop(x[gt.src], params["W_rel"], gt.etype, per_type_loop)
    agg = compat.segment_sum(msg, gt.dst, gt.num_nodes)
    deg = (gt.dst_ptr[1:] - gt.dst_ptr[:-1]).astype(agg.dtype)
    agg = agg / jnp.maximum(deg, 1.0)[:, None]
    h = agg + x @ params["W_self"]
    act = {"relu": jax.nn.relu, "tanh": jnp.tanh}[activation]
    return {"h_out": act(h)}


def rgcn_cat_vanilla(params: Dict, gt: GraphTensors, feats: Dict,
                     activation: str = "relu", per_type_loop: bool = False):
    """Concat-combine RGCN variant (models/zoo.py): concat(agg, self) @ W_out."""
    x = feats["feature"]
    msg = _maybe_loop(x[gt.src], params["W_rel"], gt.etype, per_type_loop)
    agg = compat.segment_sum(msg, gt.dst, gt.num_nodes)
    deg = (gt.dst_ptr[1:] - gt.dst_ptr[:-1]).astype(agg.dtype)
    agg = agg / jnp.maximum(deg, 1.0)[:, None]
    h = jnp.concatenate([agg, x @ params["W_self"]], axis=-1)
    h = h @ params["W_out"]
    act = {"relu": jax.nn.relu, "tanh": jnp.tanh}[activation]
    return {"h_out": act(h)}


def rgat_vanilla(params: Dict, gt: GraphTensors, feats: Dict,
                 slope: float = 0.01, per_type_loop: bool = False):
    x = feats["feature"]
    hs = _maybe_loop(x[gt.src], params["W_rel"], gt.etype, per_type_loop)
    ht = _maybe_loop(x[gt.dst], params["W_rel"], gt.etype, per_type_loop)
    atts = jnp.sum(hs * params["w_att_src"][gt.etype], axis=-1)
    attt = jnp.sum(ht * params["w_att_dst"][gt.etype], axis=-1)
    raw = atts + attt
    raw = jnp.where(raw > 0, raw, slope * raw)
    att = R.edge_softmax_ref(raw, gt.dst, gt.num_nodes)
    out = compat.segment_sum(att[:, None] * hs, gt.dst,
                             gt.num_nodes)
    return {"h_out": out}


def hgt_vanilla(params: Dict, gt: GraphTensors, feats: Dict,
                per_type_loop: bool = False):
    x = feats["feature"]
    d = params["W_K"].shape[-1]
    kk = _maybe_loop(x, params["W_K"], gt.node_type, per_type_loop)
    qq = _maybe_loop(x, params["W_Q"], gt.node_type, per_type_loop)
    vv = _maybe_loop(x, params["W_V"], gt.node_type, per_type_loop)
    katt = _maybe_loop(kk[gt.src], params["W_att"], gt.etype, per_type_loop)
    msg = _maybe_loop(vv[gt.src], params["W_msg"], gt.etype, per_type_loop)
    raw = jnp.sum(katt * qq[gt.dst], axis=-1) / jnp.sqrt(jnp.float32(d))
    att = R.edge_softmax_ref(raw, gt.dst, gt.num_nodes)
    out = compat.segment_sum(att[:, None] * msg, gt.dst,
                             gt.num_nodes)
    return {"h_out": out}


VANILLA = {"rgcn": rgcn_vanilla, "rgat": rgat_vanilla, "hgt": hgt_vanilla,
           "rgcn_cat": rgcn_cat_vanilla}
