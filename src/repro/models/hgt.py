"""HGT layers in the Hector authoring DSL: Hector's single-head form
(paper Fig. 2) and the published multi-head block (``hgt_published``).

Single head:

    k_n  = h_n W_K[τ(n)]          (nodewise typed linear, ntype segments)
    q_n  = h_n W_Q[τ(n)]
    v_n  = h_n W_V[τ(n)]
    katt = k_src W_A[τ(e)]        (edgewise typed linear -> COMPACT: the
                                   msg_HGT example of §3.2.2)
    msg  = v_src W_M[τ(e)]        (COMPACT)
    att  = softmax_dst( (katt · q_dst) / sqrt(d) )
    h_v' = Σ_e att_e · msg_e

The traced program is statement-for-statement identical to the
hand-assembled IR this module used to build (pinned by
tests/test_frontend.py).
"""
import math

from repro import frontend as hector
from repro.core.ir import inter_op as I


@hector.model
def hgt(g, e, n, in_dim, out_dim):
    W_K = g.weight("W_K", (in_dim, out_dim), indexed_by="ntype")
    W_Q = g.weight("W_Q", (in_dim, out_dim), indexed_by="ntype")
    W_V = g.weight("W_V", (in_dim, out_dim), indexed_by="ntype")
    W_A = g.weight("W_att", (out_dim, out_dim), indexed_by="etype")
    W_M = g.weight("W_msg", (out_dim, out_dim), indexed_by="etype")
    n["kk"] = n["feature"] @ W_K
    n["qq"] = n["feature"] @ W_Q
    n["vv"] = n["feature"] @ W_V
    e["katt"] = e.src["kk"] @ W_A
    e["msg"] = e.src["vv"] @ W_M
    e["att_raw"] = hector.dot(e["katt"], e.dst["qq"]) * (1.0 / math.sqrt(out_dim))
    e["att"] = hector.edge_softmax(e["att_raw"])
    n["h_out"] = hector.aggregate(e["msg"], scale=e["att"])
    return n["h_out"]


def hgt_program(in_dim: int, out_dim: int) -> I.Program:
    """Thin wrapper: trace the DSL model into inter-operator IR."""
    return hgt(in_dim, out_dim)


@hector.model
def hgt_published(g, e, n, in_dim, out_dim, heads=8):
    """HGT as published (Hu et al., WWW 2020, arXiv:2003.01332, Eqs. 3-5;
    pyHGT's ``HGTConv``): ``heads`` heads of ``in_dim / heads`` over
    node-typed K/Q/V, per-head relation matrices and priors μ, a
    target-typed A-Linear after exact GELU, and pyHGT's gated skip where
    ``in_dim == out_dim``."""
    dk = in_dim // heads
    W_K = g.weight("W_K", (in_dim, in_dim), indexed_by="ntype")
    W_Q = g.weight("W_Q", (in_dim, in_dim), indexed_by="ntype")
    W_V = g.weight("W_V", (in_dim, in_dim), indexed_by="ntype")
    W_att = g.weight("W_att", (heads, dk, dk), indexed_by="etype")
    W_msg = g.weight("W_msg", (heads, dk, dk), indexed_by="etype")
    w_prior = g.weight("w_prior", (heads,), indexed_by="etype")
    W_A = g.weight("W_A", (in_dim, out_dim), indexed_by="ntype")
    n["k"] = n["feature"] @ W_K
    n["q"] = n["feature"] @ W_Q
    n["v"] = n["feature"] @ W_V
    e["katt"] = e.src["k"] @ W_att
    e["msg"] = e.src["v"] @ W_msg
    e["att_raw"] = (hector.dot(e["katt"], e.dst["q"], heads=heads)
                    * w_prior * (1.0 / math.sqrt(dk)))
    e["att"] = hector.edge_softmax(e["att_raw"])
    n["agg"] = hector.aggregate(e["msg"], scale=e["att"])
    n["act"] = hector.gelu(n["agg"])
    if in_dim != out_dim:
        n["h_out"] = n["act"] @ W_A
        return n["h_out"]
    w_skip = g.weight("w_skip", (), indexed_by="ntype")
    n["o"] = n["act"] @ W_A
    gate = hector.sigmoid(w_skip)
    n["h_out"] = gate * n["o"] + (1.0 - gate) * n["feature"]
    return n["h_out"]


def hgt_published_program(in_dim: int, out_dim: int,
                          heads: int = 8) -> I.Program:
    """Thin wrapper: trace the DSL model into inter-operator IR."""
    return hgt_published(in_dim, out_dim, heads=heads)
