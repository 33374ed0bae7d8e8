"""Shared RGNN execution engine: graph + stack + sampler + loader wiring.

``launch/serve_rgnn.py`` used to assemble this pipeline inline (model
programs -> ``HectorStack`` -> ``FanoutSampler`` -> ``MiniBatchLoader``);
the trainer needs the identical stack, so the wiring lives here once and
both drivers build an ``RGNNEngine``. The engine owns everything that is a
pure function of (graph, model config): the lowered per-layer plans, the
compiled block executor with its compile cache, the full-graph tensors and
kernel layouts, and the fanout sampler. Traffic-dependent pieces — seed
streams, loaders, optimizer state — are created per driver via
``make_loader`` and the ``train/trainer.py`` classes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.graph import HeteroGraph
from repro.core.module import HectorStack
from repro.models import (hgt_program, hgt_published_program, rgat_program,
                          rgcn_cat_program, rgcn_program)
from repro.sampling import DeviceSampler, FanoutSampler, MiniBatchLoader

MODEL_PROGRAMS = {"rgcn": rgcn_program, "rgat": rgat_program,
                  "hgt": hgt_program, "rgcn_cat": rgcn_cat_program,
                  "hgt_published": hgt_published_program}


def parse_fanout(spec: str, layers: int) -> List[int]:
    """Parse a ``--fanout`` CLI spec: one int, or one per layer, comma
    separated; ``-1`` means the full neighborhood."""
    parts = [int(p) for p in spec.split(",")]
    if len(parts) == 1:
        parts = parts * layers
    if len(parts) != layers:
        raise ValueError(
            f"--fanout needs 1 or {layers} comma-separated ints, got {spec!r}"
        )
    return parts


@dataclasses.dataclass
class EngineConfig:
    """Model/compilation configuration shared by serving and training.

    ``model`` is a registry name (``MODEL_PROGRAMS``), a DSL-authored
    ``frontend.ModelSpec``, or any ``prog_fn(in_dim, out_dim) -> Program``
    — the ``hector.compile`` facade passes whichever the user handed it.

    ``tune`` selects the autotuning mode (``repro.tune``): ``off`` keeps the
    static lowering defaults, ``cached`` replays persisted decisions with
    zero measurements, ``full`` measures whatever the persistent cache
    (``tune_cache``, default ``~/.cache/repro-tune.json``) is missing. The
    tuner may override ``tile``/``node_block`` with its measured layout
    decision.
    """

    model: Union[str, Callable] = "rgat"
    layers: int = 2
    dim: int = 64
    hidden: int = 64
    classes: int = 16
    fanouts: Optional[Sequence] = None   # default: [5] * layers
    backend: str = "xla"
    tile: int = 32
    node_block: int = 32
    bucket: bool = True
    activation: str = "relu"
    seed: int = 0
    # "host": NumPy FanoutSampler + host layout build; "device": jit-compiled
    # sampling + layout over a device-resident CSC (same counter-based
    # selection, so both produce equivalent block streams under one seed)
    sampler: str = "host"
    # data-parallel execution: ``dp`` devices over a ``partitions``-way
    # edge-cut partition of the graph (default: one shard per device).
    # ``partitions`` may exceed ``dp`` — extra shards fold onto devices
    # (elastic shrink) with bit-identical results for any dp | partitions.
    dp: int = 1
    partitions: Optional[int] = None
    # where the node-feature table lives (repro.feats): "device" keeps the
    # full table device-resident (pre-tiering behavior), "host" keeps it in
    # per-ntype host arrays and ships only sampled rows, "cached" fronts the
    # host tier with a fixed-budget device hot-row cache. All three produce
    # bitwise-identical predictions/losses.
    feature_store: str = "device"
    # device hot-row count for feature_store="cached" (default: table/4)
    feature_budget: Optional[int] = None
    tune: str = "off"                    # off | cached | full
    tune_cache: Optional[str] = None     # persistent decision cache path
    # False for block-path-only callers (serving): keeps the materialization
    # decisions (they shape the shared lowered plans) but skips the
    # full-graph layout/op measurements serving traffic never queries
    tune_full_graph: bool = True

    def __post_init__(self):
        if isinstance(self.model, str):
            if self.model not in MODEL_PROGRAMS:
                raise ValueError(f"unknown model {self.model!r}; "
                                 f"have {sorted(MODEL_PROGRAMS)}")
        elif not callable(self.model):
            raise ValueError(
                f"model must be a registry name or a program factory "
                f"(@hector.model / prog_fn); got {type(self.model).__name__}")
        if self.tune not in ("off", "cached", "full"):
            raise ValueError(f"tune={self.tune!r}; pick off/cached/full")
        if self.sampler not in ("host", "device"):
            raise ValueError(f"sampler={self.sampler!r}; pick host/device")
        if self.feature_store not in ("device", "host", "cached"):
            raise ValueError(f"feature_store={self.feature_store!r}; "
                             f"pick device/host/cached")
        self.fanouts = list(self.fanouts) if self.fanouts is not None \
            else [5] * self.layers
        if len(self.fanouts) != self.layers:
            raise ValueError("one fanout per layer required")
        if self.dp < 1:
            raise ValueError("dp must be >= 1")
        if self.partitions is not None and self.partitions % self.dp:
            raise ValueError(
                f"partitions={self.partitions} must be a multiple of "
                f"dp={self.dp} (shards fold evenly onto devices)")

    @property
    def num_partitions(self) -> int:
        """Graph shards P (defaults to one per data-parallel device)."""
        return self.partitions if self.partitions is not None else self.dp

    @property
    def distributed(self) -> bool:
        return self.num_partitions > 1 or self.dp > 1

    @property
    def dims(self) -> List[int]:
        return [self.dim] + [self.hidden] * (self.layers - 1) + [self.classes]

    @property
    def model_name(self) -> str:
        if isinstance(self.model, str):
            return self.model
        return getattr(self.model, "name", None) \
            or getattr(self.model, "__name__", "custom")


class RGNNEngine:
    """One multi-layer RGNN compiled for one graph, ready for both
    execution modes: full-graph (``PlanExecutor`` per layer /
    ``StackTrainExecutor``) and sampled mini-batch (``BlockExecutor`` /
    ``BlockTrainExecutor``), sharing lowered plans and parameters."""

    def __init__(self, graph: HeteroGraph, cfg: EngineConfig, log=None):
        self.graph = graph
        self.cfg = cfg
        prog_fn = MODEL_PROGRAMS[cfg.model] if isinstance(cfg.model, str) \
            else cfg.model
        dims = cfg.dims
        programs = [prog_fn(dims[i], dims[i + 1]) for i in range(cfg.layers)]

        # autotuning: measured (or cache-replayed) per-op variants, per-var
        # materialization, and the kernel-layout tile — all folded into the
        # stack build below. The effective tile can differ from cfg.tile.
        self.tuner = None
        self.decisions = None
        compact_vars = None
        self.tile, self.node_block = cfg.tile, cfg.node_block
        if cfg.tune != "off":
            from repro.tune.tuner import Tuner  # lazy: pulls in codegen
            self.tuner = Tuner(mode=cfg.tune, cache_path=cfg.tune_cache,
                               log=log)
            report = self.tuner.tune_stack(
                programs, graph, backend=cfg.backend, tile=cfg.tile,
                node_block=cfg.node_block, feat_dims=dims[:-1],
                seed=cfg.seed, tune_layout=cfg.tune_full_graph,
                tune_ops=cfg.tune_full_graph)
            self.decisions = report.decisions
            compact_vars = report.compact_vars
            self.tile, self.node_block = report.tile, report.node_block

        # jit=True so the full-graph path runs through the compiled
        # PlanExecutor, not the op-by-op debug loop
        self.stack = HectorStack(
            programs, graph, backend=cfg.backend, tile=self.tile,
            node_block=self.node_block, activation=cfg.activation, jit=True,
            compact_vars=compact_vars, decisions=self.decisions,
        )
        self.sampler = FanoutSampler(graph, cfg.fanouts, seed=cfg.seed)
        # the device pipeline: uploads the CSC once at engine build; shares
        # the host sampler's seed so both paths draw the same edge streams
        self.device_sampler = None
        if cfg.sampler == "device":
            # blocks keep the configured tile (see make_loader), so the
            # device layouts match what the host pipeline would have built
            self.device_sampler = DeviceSampler(
                graph, cfg.fanouts, seed=cfg.seed,
                tile=cfg.tile, node_block=cfg.node_block,
                backend=cfg.backend)
        # compiled sampled-train-step executors, one per optimizer instance
        # (shared by the hector.compile facade and SampledTrainer so the
        # same (plans, opt) pair never compiles twice)
        self._train_execs = {}

        # data-parallel pieces: an edge-cut partition, the cross-shard
        # batcher, and a 1-D data mesh over the first ``dp`` devices. Built
        # eagerly (cheap host work) so config errors surface at compile
        # time, not on the first training step.
        self.partition = None
        self.dist_batcher = None
        self.data_mesh = None
        self._dist_execs = {}
        if cfg.distributed:
            from repro.dist import ShardedBatcher, partition_graph
            from repro.launch.mesh import make_data_mesh
            self.partition = partition_graph(graph, cfg.num_partitions)
            self.dist_batcher = ShardedBatcher(
                self.partition, cfg.fanouts, seed=cfg.seed,
                tile=cfg.tile, node_block=cfg.node_block)
            self.data_mesh = make_data_mesh(cfg.dp)

    # ------------------------------------------------------------------
    @property
    def plans(self):
        return self.stack.plans

    @property
    def block_executor(self):
        return self.stack.block_executor

    @property
    def gt(self):
        """Full-graph tensors (shared across layers)."""
        return self.stack.layers[0].gt

    @property
    def layouts(self):
        """Full-graph kernel layouts (shared across layers)."""
        return self.stack.layers[0].layouts

    def init_params(self, key: jax.Array):
        return self.stack.init(key)

    def train_executor(self, opt):
        """The compiled sampled SGD step (``BlockTrainExecutor``) for this
        engine's plans and ``opt``. Cached per optimizer instance (bounded:
        oldest entries evicted, so optimizer sweeps cannot grow memory
        without bound); a decision-table swap after (re)tuning is
        propagated instead of compiling a second executor."""
        from repro.core import executor
        ex = self._train_execs.get(id(opt))
        if ex is None:
            ex = executor.BlockTrainExecutor(
                self.plans, opt, backend=self.cfg.backend,
                activation=self.cfg.activation, decisions=self.decisions)
            self._train_execs[id(opt)] = ex
            while len(self._train_execs) > 4:   # insertion-ordered
                self._train_execs.pop(next(iter(self._train_execs)))
        if ex.decisions is not self.decisions:
            ex.set_decisions(self.decisions)
        return ex

    # ------------------------------------------------------------------
    # data-parallel surface (cfg.dp / cfg.partitions)
    # ------------------------------------------------------------------
    def _require_dist(self):
        if self.partition is None:
            raise ValueError(
                "distributed execution needs dp > 1 or partitions > 1 in "
                "the EngineConfig (e.g. hector.compile(..., dp=4))")

    def shard_features(self, feats) -> jnp.ndarray:
        """Per-owner resident feature slabs ``[P, n_own, d]``, placed once
        on the data mesh (the compiled steps all-gather them for halo
        access).

        ``feats`` may be a raw ``[N, d]`` table or a ``repro.feats`` store:
        with a store, each shard's slab is read through ``host_rows`` — the
        full table is never materialized on device, so shards hold only
        their owned rows (+ whatever the store keeps hot)."""
        self._require_dist()
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.feats import is_feature_store
        if is_feature_store(feats):
            part = self.partition
            out = np.zeros((part.num_parts, part.max_owned, feats.dim),
                           dtype=feats.dtype)
            for p in range(part.num_parts):
                lo, hi = int(part.bounds[p]), int(part.bounds[p + 1])
                out[p, : hi - lo] = feats.host_rows(
                    np.arange(lo, hi, dtype=np.int64))
        else:
            out = self.partition.shard_features(np.asarray(feats))
        # each device holds only its own shards' slabs
        return jax.device_put(out, NamedSharding(
            self.data_mesh, PartitionSpec("data")))

    def dist_serve_executor(self):
        """The compiled multi-shard inference step (cached)."""
        self._require_dist()
        ex = self._dist_execs.get("serve")
        if ex is None:
            from repro.dist import ShardedServeExecutor
            ex = ShardedServeExecutor(
                self.plans, self.data_mesh, backend=self.cfg.backend,
                activation=self.cfg.activation, decisions=self.decisions)
            self._dist_execs["serve"] = ex
        if ex.decisions is not self.decisions:
            ex.set_decisions(self.decisions)
        return ex

    def dist_train_executor(self, opt):
        """The compiled multi-shard SGD step for ``opt`` (cached per
        optimizer instance, like ``train_executor``)."""
        self._require_dist()
        ex = self._dist_execs.get(id(opt))
        if ex is None:
            from repro.dist import ShardedTrainExecutor
            ex = ShardedTrainExecutor(
                self.plans, opt, self.data_mesh, backend=self.cfg.backend,
                activation=self.cfg.activation, decisions=self.decisions)
            self._dist_execs[id(opt)] = ex
            while len(self._dist_execs) > 5:   # never evict the serve step
                self._dist_execs.pop(next(
                    k for k in self._dist_execs if k != "serve"))
        if ex.decisions is not self.decisions:
            ex.set_decisions(self.decisions)
        return ex

    # ------------------------------------------------------------------
    def make_feature_store(self, feats, *, seed_source=None,
                           probe_batches: int = 4):
        """Build the ``repro.feats`` store this config asks for
        (``cfg.feature_store`` / ``cfg.feature_budget``).

        For the cached tier, the per-ntype slot split is a *measured*
        decision when ``seed_source`` is given: ``tune.feature_budget``
        probes a few seed batches through the host sampler and splits the
        budget by observed per-ntype input-row traffic instead of raw
        populations (skewed hetero traffic rarely matches populations)."""
        from repro.feats import make_feature_store
        kind = self.cfg.feature_store
        split = None
        if kind == "cached" and seed_source is not None:
            from repro.tune.feature_budget import measured_split
            budget = self.cfg.feature_budget
            if budget is None:
                budget = max(1, self.graph.num_nodes // 4)
            split, _report = measured_split(
                self.graph, self.sampler, seed_source, budget,
                probe_batches=probe_batches)
        return make_feature_store(feats, self.graph, kind=kind,
                                  budget=self.cfg.feature_budget,
                                  split=split)

    # ------------------------------------------------------------------
    def make_loader(
        self,
        seed_source: Union[object, Callable[[int], np.ndarray]],
        *,
        num_batches: Optional[int] = None,
        start_step: int = 0,
        depth: int = 2,
        cache_blocks: int = 0,
        cache_layouts: int = 0,
        feature_store=None,
        shape_floors=None,
    ) -> MiniBatchLoader:
        """A prefetching loader over this engine's sampler/layout config.

        Blocks keep the *configured* tile (not the tuned full-graph layout
        tile): the layout decision is measured at full-graph scale and does
        not transfer to sampled-block shapes — the block-scale op variants
        are instead tuned against these layouts via ``tune_minibatch``.

        With ``cfg.sampler == "device"`` the loader gets the
        ``DeviceSampler`` and switches to the threadless async-dispatch
        prefetch (sampling + layout as enqueued device work)."""
        active = self.device_sampler if self.device_sampler is not None \
            else self.sampler
        return MiniBatchLoader(
            active, seed_source,
            tile=self.cfg.tile, node_block=self.cfg.node_block,
            bucket=self.cfg.bucket, depth=depth, start_step=start_step,
            num_batches=num_batches, cache_blocks=cache_blocks,
            cache_layouts=cache_layouts, feature_store=feature_store,
            shape_floors=shape_floors,
        )

    # ------------------------------------------------------------------
    def tune_minibatch(self, params, mb, global_feats) -> None:
        """Extend the decision table with block-scale op variants measured
        (or cache-replayed) on one representative ``MiniBatch``. Bucketed
        block shapes make the decisions valid for steady-state traffic; the
        executors pick them up via the decision-table fingerprint in their
        compile-cache keys."""
        if self.tuner is None:
            return
        self.tuner.tune_block_sequence(
            self.plans, params, mb, global_feats,
            backend=self.cfg.backend, activation=self.cfg.activation)

    @property
    def tuner_stats(self) -> dict:
        return dict(self.tuner.stats) if self.tuner is not None else {}

    # ------------------------------------------------------------------
    def forward_minibatch(self, params, mb, global_feats,
                          compiled: bool = True) -> jnp.ndarray:
        """Sampled forward: per-seed outputs for a ``MiniBatch``.

        ``global_feats`` may be the raw device table *or* any
        ``repro.feats`` store; loader-attached ``mb.feats`` win either
        way (the prefetch overlap already paid for that gather)."""
        from repro.feats import gather_input
        with obs.span("execute", step=mb.step):
            return self.stack.apply_blocks(
                params, mb, compiled=compiled,
                feats=gather_input(global_feats, mb))

    def forward_full(self, params, feats: jnp.ndarray) -> jnp.ndarray:
        """Full-graph forward (compiled per layer via ``PlanExecutor``)."""
        with obs.span("execute", mode="full_graph"):
            return self.stack.apply(params, {"feature": feats})
