"""HectorModule / HectorStack — the single-layer / multi-layer compilation
units underneath the public ``hector.compile()`` facade
(``repro.frontend``).

Direct usage (the low-level per-layer API; most callers should go through
``hector.compile`` instead):

    prog = rgat_program(in_dim=64, out_dim=64)       # traced inter-op IR
    mod = HectorModule(prog, graph, reorder=True, compact=True)
    params = mod.init(jax.random.key(0))
    out = mod.apply(params, {"feature": x})          # jitted generated code
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import codegen, executor
from repro.core.graph import HeteroGraph
from repro.core.ir import inter_op as I
from repro.core.ir.passes import lower_program


class HectorModule:
    def __init__(
        self,
        program: I.Program,
        graph: HeteroGraph,
        *,
        reorder: bool = True,
        compact: bool = True,
        compact_vars=None,
        backend: str = "xla",
        tile: int = 128,
        node_block: int = 128,
        jit: bool = True,
        gt=None,
        layouts: Optional[codegen.KernelLayouts] = None,
        decisions=None,
        layer: int = 0,
    ):
        self.program = program
        self.graph = graph
        # compact_vars (per-var materialization) and decisions (per-op
        # variants) come from the autotuner; both default to the paper's
        # static policies when absent
        self.plan = lower_program(program, reorder=reorder, compact=compact,
                                  compact_vars=compact_vars)
        # gt/layouts may be shared across modules over the same graph
        # (HectorStack builds them once for all layers)
        self.gt = graph.to_tensors() if gt is None else gt
        self.layouts = layouts if layouts is not None else \
            codegen.build_kernel_layouts(graph, tile=tile,
                                         node_block=node_block)
        self.backend = backend
        self.decisions = decisions
        self.layer = layer         # place in its stack: names the op scopes
        # whole-plan compiled executor: graph tensors and layouts flow in as
        # pytree arguments, fronted by an explicit compile cache
        self.executor = executor.PlanExecutor(
            self.plan, backend=backend, decisions=decisions,
            layer=layer) if jit else None

    # ------------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
        return codegen.init_params(self.plan, self.gt, key, dtype)

    def apply(self, params, feats: Dict[str, jnp.ndarray]):
        if self.executor is not None:
            return self.executor(params, self.gt, self.layouts, feats)
        return codegen.execute_plan(
            self.plan, params, self.gt, feats, self.layouts, self.backend,
            self.decisions, self.layer
        )

    def describe(self) -> str:
        return self.plan.describe()

    @property
    def entity_compaction_ratio(self) -> float:
        return self.graph.entity_compaction_ratio


class HectorStack:
    """A multi-layer RGNN: one Hector program per layer, with an elementwise
    activation between layers.

    Two execution paths share the same lowered plans and parameters:

    * ``apply(params, feats)``        — full-graph forward (all nodes);
    * ``apply_blocks(params, mb, x)`` — sampled mini-batch forward over a
      prefetched ``repro.sampling.MiniBatch``: one layer per hop, each over
      its block's own graph tensors/kernel layouts, returning the rows for
      the requested seeds (in request order, duplicates included).

    With full-neighborhood fanout the two paths agree within fp32 tolerance
    on the seed rows — the invariant the sampling tests pin down.
    """

    def __init__(
        self,
        programs: Sequence[I.Program],
        graph: HeteroGraph,
        *,
        reorder: bool = True,
        compact: bool = True,
        compact_vars: Optional[Sequence] = None,   # per-layer COMPACT sets
        backend: str = "xla",
        tile: int = 128,
        node_block: int = 128,
        activation: str = "relu",
        jit: bool = True,
        decisions=None,
    ):
        if not programs:
            raise ValueError("need at least one layer program")
        if compact_vars is not None and len(compact_vars) != len(programs):
            raise ValueError("need one compact-var set per layer (None to "
                             "keep a layer's default)")
        # full-graph tensors/layouts are identical across layers: build once
        gt = graph.to_tensors()
        layouts = codegen.build_kernel_layouts(graph, tile=tile,
                                               node_block=node_block)
        self.layers = [
            HectorModule(p, graph, reorder=reorder, compact=compact,
                         compact_vars=(None if compact_vars is None
                                       else compact_vars[i]),
                         backend=backend, tile=tile, node_block=node_block,
                         jit=jit, gt=gt, layouts=layouts,
                         decisions=decisions, layer=i)
            for i, p in enumerate(programs)
        ]
        self.activation = activation
        self.backend = backend
        self.jit = jit
        self.decisions = decisions
        self._act = codegen._ACTIVATIONS[activation]
        # whole-plan compiled executor over the entire block sequence (all
        # hops in one jitted callable, fronted by a compile cache keyed on
        # the bucketed layout shapes) — the serving hot path
        self.block_executor = executor.BlockExecutor(
            self.plans, backend=backend, activation=activation,
            decisions=decisions)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def plans(self):
        return [l.plan for l in self.layers]

    # ------------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> List[Dict[str, jnp.ndarray]]:
        keys = jax.random.split(key, self.num_layers)
        return [l.init(k, dtype) for l, k in zip(self.layers, keys)]

    def apply(self, params: Sequence[Dict[str, jnp.ndarray]],
              feats: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Full-graph forward; returns the last layer's primary output."""
        cur = dict(feats)
        h = None
        for i, (layer, p) in enumerate(zip(self.layers, params)):
            out = layer.apply(p, cur)
            h = out[layer.plan.outputs[0]]
            if i < self.num_layers - 1:
                cur = {"feature": self._act(h)}
        return h

    def apply_blocks(self, params: Sequence[Dict[str, jnp.ndarray]],
                     mb, global_feats: Optional[jnp.ndarray] = None,
                     compiled: Optional[bool] = None,
                     feats: Optional[Dict[str, jnp.ndarray]] = None
                     ) -> jnp.ndarray:
        """Sampled forward over a ``MiniBatch``; returns [len(seeds), out].

        ``compiled=True`` runs the whole block sequence through the jitted
        ``BlockExecutor`` (cache-hit on repeated bucketed shapes);
        ``compiled=False`` is the op-by-op eager loop for debugging. The
        default follows the stack's ``jit`` flag.

        Input features come from ``feats`` (an explicit pre-gathered
        pytree), else ``mb.feats`` (attached by a feature-store-wired
        loader), else an on-device gather from ``global_feats``.
        """
        if compiled is None:
            compiled = self.jit
        if mb.num_hops != self.num_layers:
            raise ValueError(
                f"minibatch has {mb.num_hops} hops but the stack has "
                f"{self.num_layers} layers"
            )
        if compiled:
            return self.block_executor.run_minibatch(
                list(params), mb, global_feats, feats=feats)
        if feats is None:
            feats = getattr(mb, "feats", None)
        if feats is None:
            feats = {"feature": global_feats[mb.input_ids]}
        return codegen.execute_block_sequence(
            self.plans, list(params), mb.tensors, mb.layouts, mb.dst_locals,
            mb.seed_perm, feats, backend=self.backend,
            activation=self.activation, decisions=self.decisions,
        )
