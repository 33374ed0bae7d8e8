"""Hector code generator (paper §3.6), TPU/JAX adaptation.

GPU Hector emits CUDA kernels + host functions from intra-operator IR specs.
The JAX equivalent of "emitting code" is building **closed jitted callables**:
each ``GemmSpec`` instantiates the segment-MM kernel (Pallas) or its XLA
formulation with the access schemes baked in; each ``TraversalSpec`` executes
its fused statement region, pattern-matching the canonical fused
edge-softmax(+aggregate) region onto the fused traversal kernel. Fallbacks
run as plain jnp ops (the "PyTorch fallback" of §3.2.5).

Auto-differentiation: the paper pairs hand-written backward kernels via
``autograd.Function`` (§3.5); here every kernel op carries a ``custom_vjp``
whose backward is itself template-derived (outer-product GEMM instances for
dW, traversal instances for feature grads) — see kernels/ops.py.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np

from repro import compat
from repro.core.graph import GraphTensors, HeteroGraph
from repro.core.ir import inter_op as I
from repro.core.ir import intra_op as O
from repro.kernels import layout as L
from repro.kernels import ops as K
from repro.tune import device as tunedev
from repro.tune import space as tspace


@dataclasses.dataclass(frozen=True, eq=False)
class KernelLayouts:
    """Per-graph tile-aligned layouts for the generated kernels (host-built).

    Besides the segment/CSR layouts this carries the *padded gather-index
    layouts* (§3.3 access schemes composed with the tile padding maps), so
    the Pallas kernels can gather their input rows in-kernel, the
    precomputed per-destination in-degree used by mean aggregation, and the
    message-row layout over which the backward of an unfused compact
    message gather sums (None where no such backward is built).
    Registered as a pytree (metadata static) so whole plans can be jitted
    with the layouts as run-time arguments.
    """

    edge_seg: K.PaddedSegmentsDev      # etype segments over canonical edges
    unique_seg: K.PaddedSegmentsDev    # etype segments over unique (src,etype)
    node_seg: K.PaddedSegmentsDev      # ntype segments over nodes
    blocked: K.BlockedCSRDev           # dst-sorted blocked CSR
    edge_src_rows: jnp.ndarray         # [Rp_e] padded slot -> src node, or -1
    edge_dst_rows: jnp.ndarray         # [Rp_e] padded slot -> dst node, or -1
    unique_src_rows: jnp.ndarray       # [Rp_u] padded slot -> src node, or -1
    dst_deg: jnp.ndarray               # [N] float32 per-destination in-degree
    msg_rev: Optional[K.BlockedCSRDev] = None  # edges sorted by unique row


_KL_FIELDS = ("edge_seg", "unique_seg", "node_seg", "blocked",
              "edge_src_rows", "edge_dst_rows", "unique_src_rows", "dst_deg",
              "msg_rev")

jtu.register_pytree_node(
    KernelLayouts,
    lambda kl: (tuple(getattr(kl, f) for f in _KL_FIELDS), None),
    lambda aux, ch: KernelLayouts(*ch),
)


def build_kernel_layouts(
    hg: HeteroGraph, tile: int = 128, node_block: int = 128,
    bucket: bool = False, row_floors=None,
) -> KernelLayouts:
    """Build the per-graph layouts; with ``bucket=True`` every layout is
    grown to power-of-two row/edge-slot counts (pure padding), so repeated
    compilation caches hit across sampled blocks of different sizes.

    The segment-row buckets depend on how edges distribute across
    segments, not just the graph's padded totals, so blocks sharing one
    (node, edge, unique) bucket combination can still disagree here.
    ``row_floors`` (a ``bucketing.LayoutRowFloors``) clamps each field's
    bucket to a grow-only floor shared across blocks, pinning the layout
    shapes the way ``pad_block_graph`` targets pin the graph shapes.
    Bucketed layouts serve sampled blocks, whose aggregations gather
    in-kernel, and carry no message-row layout."""
    edge_ps = L.pad_segments(hg.etype_ptr, tile)
    unique_ps = L.pad_segments(hg.unique_etype_ptr, tile)
    node_ps = L.pad_segments(hg.ntype_ptr, tile)
    bc = L.block_csr(hg.dst_ptr, edge_tile=tile, node_block=node_block)
    if bucket:
        if tile & (tile - 1):
            raise ValueError("bucketed layouts need a power-of-two tile")

        def bucket_rows(name: str, rows: int) -> int:
            t = max(tile, L.pow2ceil(rows))
            if row_floors is not None:
                t = row_floors.raise_to(name, t)
            return t
        edge_ps = L.pad_segments_rows(
            edge_ps, bucket_rows("edge", edge_ps.padded_rows))
        unique_ps = L.pad_segments_rows(
            unique_ps, bucket_rows("unique", unique_ps.padded_rows))
        node_ps = L.pad_segments_rows(
            node_ps, bucket_rows("node", node_ps.padded_rows))
        bc = L.pad_blocked_csr(bc, bucket_rows("csr", bc.padded_edges))
    return KernelLayouts(
        edge_seg=K.padded_segments_dev(edge_ps),
        unique_seg=K.padded_segments_dev(unique_ps),
        node_seg=K.padded_segments_dev(node_ps),
        blocked=K.blocked_csr_dev(bc, hg.perm_dst, hg.edge_to_unique),
        edge_src_rows=jnp.asarray(L.compose_gather_rows(edge_ps, hg.src)),
        edge_dst_rows=jnp.asarray(L.compose_gather_rows(edge_ps, hg.dst)),
        unique_src_rows=jnp.asarray(
            L.compose_gather_rows(unique_ps, hg.unique_src)),
        dst_deg=jnp.asarray(np.diff(hg.dst_ptr).astype(np.float32)),
        msg_rev=None if bucket else K.msg_rev_dev(
            hg.edge_to_unique, hg.num_unique, tile),
    )


# ---------------------------------------------------------------------------
# parameter initialization from the plan's weight table
# ---------------------------------------------------------------------------
def init_params(
    plan: O.Plan, gt: GraphTensors, key: jax.Array, dtype=jnp.float32
) -> Dict[str, jnp.ndarray]:
    params: Dict[str, jnp.ndarray] = {}
    names = sorted(n for n in plan.weights if not n.startswith("_wprod"))
    keys = jax.random.split(key, max(1, len(names)))
    for k, name in zip(keys, names):
        w = plan.weights[name]
        if w.indexed_by == "etype":
            lead = (gt.num_etypes,)
        elif w.indexed_by in ("ntype", "ntype_src", "ntype_dst"):
            lead = (gt.num_ntypes,)
        else:
            lead = ()
        shape = lead + tuple(w.shape)
        # a per-head matrix (H, k, n) takes k; a matrix or vector its first
        fan_in = w.shape[-2] if len(w.shape) >= 3 else (
            w.shape[0] if len(w.shape) >= 1 else 1)
        scale = 1.0 / math.sqrt(max(1, fan_in))
        params[name] = (jax.random.normal(k, shape) * scale).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# the generated forward function
# ---------------------------------------------------------------------------
_SOFTMAX_TAIL = ("segment_max", "gather_dst_var", "elementwise", "elementwise",
                 "segment_sum", "gather_dst_var", "elementwise")


class _Env:
    """Execution environment: name -> array, with layout-aware edge reads."""

    def __init__(self, plan: O.Plan, gt: GraphTensors, params, feats):
        self.plan = plan
        self.gt = gt
        self.vals: Dict[str, jnp.ndarray] = {}
        for name, v in feats.items():
            self.vals["node:" + name] = v
        self.params = dict(params)

    def get(self, name: str) -> jnp.ndarray:
        if name.startswith("scalar:"):
            return jnp.float32(float(name.split(":", 1)[1]))
        if name in self.vals:
            return self.vals[name]
        if name.startswith("node:") and name[5:] in self.vals:
            return self.vals[name[5:]]
        raise KeyError(f"undefined IR value {name!r}; have {list(self.vals)}")

    def get_edge_vanilla(self, name: str) -> jnp.ndarray:
        """Read an edge var in canonical per-edge order, resolving compact
        layout through the edge_to_unique indirection."""
        v = self.get(name)
        if self.plan.layouts.get(name) == I.Layout.COMPACT:
            return v[self.gt.edge_to_unique]
        return v

    def set(self, name: str, v: jnp.ndarray):
        self.vals[name] = v


def _elementwise(op: str, args, alpha: float = 0.01):
    a = args[0]
    if len(args) == 1:
        if op == "exp":
            return jnp.exp(a)
        if op == "leaky_relu":
            return jnp.where(a > 0, a, alpha * a)
        if op == "relu":
            return jnp.maximum(a, 0)
        if op == "sigmoid":
            return jax.nn.sigmoid(a)
        if op == "tanh":
            return jnp.tanh(a)
        if op == "gelu":
            return jax.nn.gelu(a, approximate=False)
        if op == "neg":
            return -a
        raise ValueError(op)
    b = args[1]
    if a.ndim == 2 and b.ndim == 1:
        b = b[:, None]
    elif a.ndim == 1 and b.ndim == 2:
        a = a[:, None]
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "div":
        return a / b
    raise ValueError(op)


def _scope_part(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", name)


def op_scope(op, layer: int = 0) -> str:
    """The ``jax.named_scope`` an op spec's device work carries:
    ``l<layer>.<kind>.<output>`` (``l1.traversal.h_out``). Compiled
    instructions keep it in their ``op_name`` metadata, under
    ``transpose(...)`` in the backward pass (``obs/device_ops.py``).

    Kinds: ``gemm`` (edgewise or untyped GEMMs), ``nodegemm`` (GEMMs over
    nodes with node-typed weights), ``traversal`` (edge regions: scores,
    edge softmax, aggregation), ``nodewise`` (elementwise over nodes),
    ``wprod``, ``fallback``."""
    if isinstance(op, O.TraversalSpec):
        kind = ("nodewise" if op.domain == O.LoopDomain.NODES
                else "traversal")
        out = op.stmts[-1].out
    elif isinstance(op, O.GemmSpec):
        kind = ("nodegemm" if op.type_index == O.TypeIndex.NTYPE
                else "gemm")
        out = op.out
    elif isinstance(op, O.WeightProductSpec):
        kind, out = "wprod", op.out
    else:
        kind, out = "fallback", op.kid
    return f"l{layer}.{kind}.{_scope_part(out)}"


def output_scope(plan: O.Plan, layer: int) -> str:
    """Scope of a layer's output handling between layers (frontier
    narrowing, activation, the final seed gather)."""
    return f"l{layer}.output.{_scope_part(plan.outputs[0])}"


def execute_plan(
    plan: O.Plan,
    params: Dict[str, jnp.ndarray],
    gt: GraphTensors,
    feats: Dict[str, jnp.ndarray],
    kl: KernelLayouts,
    backend: str = "xla",
    decisions=None,
    layer: int = 0,
) -> Dict[str, jnp.ndarray]:
    """Run the lowered layer. Returns {output name: array}.

    ``decisions`` is an optional ``tune.TuningDecisions`` table; op
    instances found in it dispatch on the recorded variant (backend, tile
    shape, gather fusion) instead of the hardcoded defaults. ``layer``
    names the ops' scopes (``op_scope``).
    """
    env = _Env(plan, gt, params, feats)
    derived: Dict[str, jnp.ndarray] = {}
    for op in plan.ops:
        execute_op(op, env, derived, gt, kl, backend, decisions, layer)
    return {name: env.get(name) for name in plan.outputs}


def execute_op(op, env: _Env, derived: Dict[str, jnp.ndarray],
               gt: GraphTensors, kl: KernelLayouts, backend: str = "xla",
               decisions=None, layer: int = 0) -> None:
    """Execute ONE lowered op spec against the environment — the loop body
    of ``execute_plan``, factored out so the obs profiler can advance a
    plan op by op and time each instance individually. The op runs under
    its ``op_scope``.

    ``derived`` carries hoisted weight products (``WeightProductSpec``
    outputs) that later GEMMs resolve before the parameter table.
    """
    with jax.named_scope(op_scope(op, layer)):
        _execute_op(op, env, derived, gt, kl, backend, decisions)


def _execute_op(op, env, derived, gt, kl, backend, decisions) -> None:
    if isinstance(op, O.WeightProductSpec):
        wm, wv = env.params[op.w_matrix], env.params[op.w_vector]
        # (x W_r) · w_r == x (W_r w_r^T): hoisted weight-weight BMM
        derived[op.out] = jnp.einsum("rdf,rf->rd", wm, wv)[..., None]
    elif isinstance(op, O.GemmSpec):
        _exec_gemm(op, env,
                   lambda name: derived.get(name, env.params.get(name)),
                   gt, kl, backend, decisions)
    elif isinstance(op, O.TraversalSpec):
        _exec_traversal(op, env, gt, kl, backend, decisions)
    elif isinstance(op, O.FallbackSpec):
        raise NotImplementedError(
            f"fallback op {op.stmt} reached the executor; add a jnp "
            f"lowering for it"
        )


# ---------------------------------------------------------------------------
# block-sequence execution (sampled mini-batch path)
# ---------------------------------------------------------------------------
_ACTIVATIONS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "none": lambda x: x,
    None: lambda x: x,
}


def execute_block_sequence(
    plans,                  # List[O.Plan], one lowered layer per hop
    params,                 # List[Dict[str, jnp.ndarray]] per layer
    gts,                    # List[GraphTensors] per block
    kls,                    # List[KernelLayouts] per block
    dst_locals,             # List[jnp.ndarray]: out-frontier rows per block
    seed_perm: jnp.ndarray,  # final-frontier row of each requested seed
    feats: Dict[str, jnp.ndarray],  # features for the first block's node set
    backend: str = "xla",
    activation: str = "relu",
    decisions=None,
) -> jnp.ndarray:
    """Run one lowered layer per sampled hop, narrowing to each hop's output
    frontier, and gather the requested seed rows from the last hop.

    The mini-batch analogue of ``execute_plan``: every hop executes the same
    generated code over its block's own ``GraphTensors``/``KernelLayouts``
    (which are just smaller instances of the full-graph products), and the
    host-precomputed ``dst_locals`` maps align hop l's outputs with hop
    l+1's node set.
    """
    if not (len(plans) == len(params) == len(gts) == len(kls)
            == len(dst_locals)):
        raise ValueError("plans/params/blocks length mismatch")
    act = _ACTIVATIONS[activation]
    cur = dict(feats)
    h = None
    last = len(plans) - 1
    for i, (plan, p, gt, kl) in enumerate(zip(plans, params, gts, kls)):
        out = execute_plan(plan, p, gt, cur, kl, backend, decisions, i)
        with jax.named_scope(output_scope(plan, i)):
            h = out[plan.outputs[0]][dst_locals[i]]
            if i < last:
                cur = {"feature": act(h)}
            else:
                h = h[seed_perm]
    return h


# gather schemes whose row lists have a precomposed padded gather-index
# layout in KernelLayouts (-> eligible for the in-kernel gather kernels)
_FUSABLE_GATHERS = (O.GatherScheme.BY_EDGE_SRC, O.GatherScheme.BY_EDGE_DST,
                    O.GatherScheme.BY_UNIQUE_SRC)


def _gather_fits(src, index_map=None, tile: int = 1, heads: int = 1) -> bool:
    """Gather-fusion gate: the ungathered source block must fit the VMEM
    budget and the scalar-prefetched slot map (+ per-tile table) SMEM,
    each against the capacity of the device that runs the kernel
    (``tune/device.py``). With ``heads`` the kernel sees ``heads`` times the
    rows and slots at ``1/heads`` of the width (``ops._per_head``)."""
    slots = 0 if index_map is None else int(index_map.size)
    return tunedev.fused_gather_fits(
        heads * src.shape[0], src.shape[-1] // heads, src.dtype.itemsize,
        heads * slots, tile)


def _fuse(dec, fits: bool) -> bool:
    """A tuned decision may turn gather fusion off, never on past the
    gate: a kernel that does not fit is never handed to the compiler."""
    if dec is not None and dec.fuse_gather is not None:
        return dec.fuse_gather and fits
    return fits


def _gemm_decision(decisions, op, lay, x_src, w, has_scale):
    if decisions is None or lay is None:
        return None
    key = tspace.gemm_key(op, lay, int(x_src.shape[0]), int(w.shape[-2]),
                          int(w.shape[-1]), has_scale, x_src.dtype)
    return decisions.lookup(key)


def _trav_decision(decisions, kind, msg, compact_msg, kl):
    if decisions is None:
        return None
    key = tspace.trav_key(kind, int(msg.shape[-1]), compact_msg, kl.blocked,
                          msg.dtype)
    return decisions.lookup(key)


def _exec_gemm(op: O.GemmSpec, env: _Env, weight, gt: GraphTensors,
               kl: KernelLayouts, backend: str, decisions=None):
    w = weight(op.weight)
    per_head = op.heads > 1
    if w.ndim == 4 and not per_head:
        w = w[:, 0]          # one head: a plain typed linear

    scale = None
    if op.per_row_scale is not None:
        scale = env.get_edge_vanilla(op.per_row_scale)
        if scale.ndim == 2:
            scale = scale[:, 0]

    # resolve the access scheme: layout, padded gather map, gather list
    if op.gather == O.GatherScheme.BY_EDGE_SRC:
        lay, gmap, gidx = kl.edge_seg, kl.edge_src_rows, gt.src
        x_src = env.get(op.x_source)
    elif op.gather == O.GatherScheme.BY_EDGE_DST:
        lay, gmap, gidx = kl.edge_seg, kl.edge_dst_rows, gt.dst
        x_src = env.get(op.x_source)
    elif op.gather == O.GatherScheme.BY_UNIQUE_SRC:
        lay, gmap, gidx = kl.unique_seg, kl.unique_src_rows, gt.unique_src
        x_src = env.get(op.x_source)
    elif op.gather == O.GatherScheme.BY_NODE:
        lay, gmap, gidx = kl.node_seg, None, None
        x_src = env.get(op.x_source)
    else:  # IDENTITY: var already in segment-sorted order
        x_src = env.get(op.x_source.split(":", 1)[1]
                        if op.x_source.startswith("edge:") else op.x_source)
        lay = {
            "etype_ptr": kl.edge_seg,
            "unique_etype_ptr": kl.unique_seg,
            "ntype_ptr": kl.node_seg,
        }.get(op.seg_ptr)
        gmap = gidx = None

    typed = op.type_index != O.TypeIndex.NONE
    # per-head GEMMs take the static defaults: tuned variants are keyed by
    # one-head shapes
    dec = _gemm_decision(decisions, op, lay, x_src, w, scale is not None) \
        if typed and not per_head else None
    backend_eff = backend
    tile_rows = tile_n = None
    if dec is not None:
        if dec.backend != tspace.DEFAULT:
            backend_eff = dec.backend
        tile_rows, tile_n = dec.tile_rows, dec.tile_n

    # Pallas backends with a typed GEMM: fold the access-scheme gather into
    # the kernel via the padded gather-index layout — the [rows, k] input
    # copy is never materialized outside the kernel (paper §3.3).
    if (backend_eff != "xla" and typed and gmap is not None
            and op.gather in _FUSABLE_GATHERS and not per_head):
        if _fuse(dec, _gather_fits(x_src, gmap, tile_rows or lay.tile)):
            y = K.segment_mm_gather(x_src, w, lay, gmap, row_scale=scale,
                                    backend=backend_eff,
                                    tile_n=tile_n or 128,
                                    tile_rows=tile_rows)
            out = y[:, 0] if (op.out_cols == 1 and y.shape[-1] == 1) else y
            env.set(op.out, out)
            return

    # materialized gather (XLA fuses the gather into the consumer)
    x = x_src if gidx is None else x_src[gidx]
    if not typed:
        y = x @ w
        if scale is not None:
            y = y * scale[:, None]
    else:
        y = K.segment_mm(x, w, lay, row_scale=scale, backend=backend_eff,
                         tile_n=tile_n or 128, tile_rows=tile_rows)
    out = y[:, 0] if (op.out_cols == 1 and y.shape[-1] == 1) else y
    env.set(op.out, out)


def _edge_msg(env: _Env, gt: GraphTensors, kl: KernelLayouts, name: str):
    """Resolve a feature-wide edge var in its *storage* order for the
    traversal kernels: COMPACT vars stay in the unique-pair table and carry
    the precomposed slot map, so the per-edge expansion happens in-kernel
    instead of materializing an [E, d] copy here. -> (messages, msg_rows,
    slot map, message-row layout)."""
    v = env.get(name)
    if env.plan.layouts.get(name) == I.Layout.COMPACT:
        return v, gt.edge_to_unique, kl.blocked.edge_map_unique, kl.msg_rev
    return v, None, kl.blocked.edge_map, None


def _edge_scalars(v: jnp.ndarray) -> jnp.ndarray:
    """An edge score or scale as the traversal ops take it: ``[E]`` for one
    head (a ``[E, 1]`` column squeezed), ``[E, H]`` for H heads."""
    return v[:, 0] if v.ndim == 2 and v.shape[1] == 1 else v


def _exec_traversal(op: O.TraversalSpec, env: _Env, gt: GraphTensors,
                    kl: KernelLayouts, backend: str, decisions=None):
    """Execute a fused traversal region, fusing the canonical softmax(+agg)
    pattern onto the Pallas traversal kernel when present."""
    stmts = op.stmts
    i = 0
    while i < len(stmts):
        # peephole: expanded softmax (7 stmts) [+ segment_sum scaled by it]
        if (
            i + len(_SOFTMAX_TAIL) <= len(stmts)
            and tuple(s.kind for s in stmts[i : i + 7]) == _SOFTMAX_TAIL
        ):
            score_name = stmts[i].ins[0]
            att_name = stmts[i + 6].out
            scores = _edge_scalars(env.get_edge_vanilla(score_name))
            heads = 1 if scores.ndim == 1 else scores.shape[1]
            nxt = stmts[i + 7] if i + 7 < len(stmts) else None
            if (
                nxt is not None
                and nxt.kind == "segment_sum"
                and nxt.scale == att_name
            ):
                msg, msg_rows, slot_map, rev = _edge_msg(env, gt, kl,
                                                         nxt.ins[0])
                dec = _trav_decision(decisions, "softmax_agg", msg,
                                     msg_rows is not None, kl) \
                    if heads == 1 else None
                backend_eff = backend
                if dec is not None and dec.backend != tspace.DEFAULT:
                    backend_eff = dec.backend
                if backend_eff != "xla":
                    # fully fused softmax+aggregate traversal kernel
                    fuse = _fuse(dec, _gather_fits(
                        msg, slot_map, kl.blocked.edge_tile, heads))
                    out = K.edge_softmax_agg(
                        scores, msg, gt.dst, gt.num_nodes,
                        bc=kl.blocked, backend=backend_eff,
                        msg_rows=msg_rows, msg_slot_map=slot_map,
                        fuse_gather=fuse, msg_rev=rev,
                    )
                    env.set(nxt.out, out)
                    env.set(att_name,
                            K.edge_softmax(scores, gt.dst, gt.num_nodes))
                    i += 8
                    continue
            env.set(att_name, K.edge_softmax(scores, gt.dst, gt.num_nodes))
            i += 7
            continue

        s = stmts[i]
        if s.kind == "elementwise":
            args = [env.get_edge_vanilla(a) if not a.startswith(("node:", "scalar:"))
                    else env.get(a) for a in s.ins]
            env.set(s.out, _elementwise(s.op, args, s.alpha))
        elif s.kind == "rowdot":
            a = env.get_edge_vanilla(s.ins[0])
            b = env.get_edge_vanilla(s.ins[1])
            if s.heads == 1:
                env.set(s.out, jnp.sum(a * b, axis=-1))
            else:   # one dot per head over its block of columns
                env.set(s.out, jnp.sum(
                    (a * b).reshape(a.shape[0], s.heads, -1), axis=-1))
        elif s.kind == "concat":
            env.set(s.out, jnp.concatenate(
                [env.get_edge_vanilla(a) for a in s.ins], axis=-1))
        elif s.kind == "gather_src":
            env.set(s.out, env.get(s.ins[0])[gt.src])
        elif s.kind == "gather_dst":
            env.set(s.out, env.get(s.ins[0])[gt.dst])
        elif s.kind == "gather_dst_var":
            env.set(s.out, env.get(s.ins[0])[gt.dst])
        elif s.kind == "gather_unique":
            env.set(s.out, env.get(s.ins[0])[gt.edge_to_unique])
        elif s.kind == "gather_etype_weight":
            env.set(s.out, env.params[s.ins[0]][gt.etype])
        elif s.kind == "gather_ntype_weight":
            env.set(s.out, env.params[s.ins[0]][gt.node_type])
        elif s.kind == "segment_max":
            x = env.get_edge_vanilla(s.ins[0])
            mx = compat.segment_max(x, gt.dst, gt.num_nodes)
            env.set(s.out, jnp.where(jnp.isfinite(mx), mx, 0.0))
        elif s.kind == "segment_sum":
            msg, msg_rows, slot_map, rev = _edge_msg(env, gt, kl, s.ins[0])
            scale = None
            if s.scale is not None:
                scale = _edge_scalars(env.get_edge_vanilla(s.scale))
            heads = 1 if scale is None or scale.ndim == 1 else scale.shape[1]
            dec = _trav_decision(decisions, "weighted_agg", msg,
                                 msg_rows is not None, kl) \
                if heads == 1 else None
            backend_eff = backend
            if dec is not None and dec.backend != tspace.DEFAULT:
                backend_eff = dec.backend
            fuse = _fuse(dec, _gather_fits(msg, slot_map,
                                         kl.blocked.edge_tile, heads))
            out = K.weighted_agg(scale, msg, gt.dst, gt.num_nodes,
                                 bc=kl.blocked, backend=backend_eff,
                                 msg_rows=msg_rows, msg_slot_map=slot_map,
                                 fuse_gather=fuse, msg_rev=rev)
            if s.op == "mean":
                deg = kl.dst_deg.astype(out.dtype)
                out = out / jnp.maximum(deg, 1.0)[:, None]
            env.set(s.out, out)
        else:
            raise NotImplementedError(f"traversal stmt {s.kind}")
        i += 1
