"""Prefetching mini-batch loader for sampled RGNN blocks.

Mirrors the queue pattern of ``data/pipeline.py``: a background thread pulls
seed batches from a deterministic stream, runs the fanout sampler, and —
crucially — builds the tile-aligned ``KernelLayouts`` for every block on the
host, off the accelerator path. The consumer (training or serving loop) only
ever dequeues device-ready ``MiniBatch`` bundles, so layout construction
(NumPy segment padding / CSR blocking) overlaps with accelerator compute.

Serving traffic is power-law, so the loader layers two LRU caches over that
pipeline (ROADMAP "cached neighbor layouts"):

* a **KernelLayouts cache** keyed by block signature (a content hash of the
  block graph's edge/node-type arrays plus the tile/bucket config) — blocks
  that sample the same subgraph skip the NumPy padding/CSR-blocking passes;
* a **sampled-block cache** keyed by ``(seeds, fanout)`` — repeated seed
  batches skip sampling *and* layout construction entirely and return the
  previously built device-ready ``MiniBatch``.

Hit/miss counters are exposed (``cache_stats``) so the serving driver and
benchmarks can report and assert steady-state reuse.

**Device mode**: constructed with a ``DeviceSampler`` (anything exposing
``sample_minibatch``) instead of a ``FanoutSampler``, the loader switches to
a threadless prefetch: sampling and layout construction are jit-compiled
device programs whose dispatch is asynchronous, so overlapping batch k+1's
sampling with batch k's execution only requires *dispatching* k+1 before the
consumer executes k — two interleaved streams of enqueued device work, no
producer thread. ``host_builds`` / ``device_builds`` count which pipeline
actually built each non-cached batch, so benchmarks can assert the device
steady state performs zero host-side sampling or layout work.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import queue
import threading
from typing import Callable, List, Optional, Union

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core import codegen
from repro.core.graph import GraphTensors, HeteroGraph
from repro.kernels.layout import pow2ceil
from repro.sampling.bucketing import pad_block_graph, pad_index
from repro.sampling.sampler import BlockSequence, FanoutSampler


class LRUCache:
    """Minimal LRU map with hit/miss/eviction counters (single-consumer:
    each loader's producer thread owns its caches, so no locking).

    ``name`` labels the cache in the obs metrics registry: every hit, miss,
    and eviction is mirrored to ``loader_cache_{hits,misses,evictions}``
    counters with a ``cache=<name>`` label when metrics are enabled (the
    plain integer attributes remain the always-on source of truth)."""

    def __init__(self, maxsize: int = 64, name: str = "lru"):
        if maxsize <= 0:
            raise ValueError("LRUCache needs a positive maxsize")
        self.maxsize = maxsize
        self.name = name
        self._d: "collections.OrderedDict" = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        try:
            v = self._d.pop(key)
        except KeyError:
            self.misses += 1
            obs.metrics().counter("loader_cache_misses",
                                  cache=self.name).inc()
            self._mirror_rate()
            return None
        self._d[key] = v          # re-insert: most recently used
        self.hits += 1
        obs.metrics().counter("loader_cache_hits", cache=self.name).inc()
        self._mirror_rate()
        return v

    def _mirror_rate(self) -> None:
        # registry snapshots carry the *rate*, not just raw counters, so CI
        # gates and dashboards read reuse directly (ISSUE 9 satellite)
        obs.metrics().gauge("loader_cache_hit_rate",
                            cache=self.name).set(self.hit_rate)

    def put(self, key, value) -> None:
        self._d.pop(key, None)
        self._d[key] = value
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
            self.evictions += 1
            obs.metrics().counter("loader_cache_evictions",
                                  cache=self.name).inc()

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": len(self._d),
                "hit_rate": self.hit_rate}


def block_signature(hg: HeteroGraph, tile: int, node_block: int,
                    bucket: bool) -> tuple:
    """Content key for a block graph's kernel layouts: two blocks with equal
    signatures produce identical ``KernelLayouts`` (all layout products are
    pure functions of the edge arrays, node types, and the tile config)."""
    h = hashlib.blake2b(digest_size=16)
    for arr in (hg.src, hg.dst, hg.etype, hg.node_type):
        h.update(np.ascontiguousarray(arr).tobytes())
    return (hg.num_nodes, hg.num_ntypes, hg.num_etypes,
            tile, node_block, bool(bucket), h.digest())


class SeedStream:
    """Deterministic seed-node request stream: step -> seed ID batch.

    Models a serving request stream (seeds drawn with replacement, so
    duplicate seeds within a batch are exercised). ``batch(step)`` is a pure
    function of (seed, step), the same restart-determinism contract as
    ``SyntheticLMStream``.

    ``num_distinct`` models power-law / repeating traffic: steps wrap onto
    ``step % num_distinct``, so the stream cycles over a fixed set of seed
    batches — the workload shape that makes the sampled-block and layout
    caches (and the compiled-executor cache) pay off.

    ``zipf_alpha`` draws seeds from a Zipf distribution over the node
    population instead of uniformly: node popularity rank ``r`` (0-based)
    has probability proportional to ``(r + 1) ** -alpha``, and a
    seed-keyed permutation maps ranks onto ids so the hot set is spread
    across the id space (not just the lowest ids). This is the realistic
    skewed-traffic model the feature-cache benchmarks run against; with
    ``alpha`` ~1.0-1.5 a small device hot-row cache absorbs most of the
    feature traffic. ``batch(step)`` stays a pure function of
    ``(seed, step)`` — the rank table is built once from the seed.

    ``ids`` restricts the population to an explicit id set (e.g. a train
    split) instead of ``[0, num_nodes)``.
    """

    def __init__(self, num_nodes: Optional[int] = None,
                 batch_size: int = 32, seed: int = 0,
                 num_distinct: Optional[int] = None,
                 zipf_alpha: Optional[float] = None,
                 ids: Optional[np.ndarray] = None):
        if ids is not None:
            self.ids = np.asarray(ids, dtype=np.int32)
            if self.ids.ndim != 1 or self.ids.size == 0:
                raise ValueError("ids must be a non-empty 1-D int array")
            self.num_nodes = int(self.ids.size)
        else:
            if num_nodes is None:
                raise ValueError("need num_nodes or ids")
            self.ids = None
            self.num_nodes = int(num_nodes)
        self.batch_size = batch_size
        self.seed = seed
        self.num_distinct = num_distinct
        self.zipf_alpha = zipf_alpha
        self._cdf = self._rank2idx = None
        if zipf_alpha is not None:
            if zipf_alpha <= 0:
                raise ValueError("zipf_alpha must be positive")
            p = np.arange(1, self.num_nodes + 1,
                          dtype=np.float64) ** -float(zipf_alpha)
            self._cdf = np.cumsum(p / p.sum())
            # popularity rank -> population index, keyed off the stream
            # seed so the hot rows aren't simply the lowest ids
            self._rank2idx = np.random.default_rng(
                (self.seed, 0x5eed)).permutation(
                self.num_nodes).astype(np.int64)

    def batch(self, step: int) -> np.ndarray:
        if self.num_distinct:
            step = step % self.num_distinct
        rng = np.random.default_rng((self.seed, step))
        if self._cdf is None:
            # identical draws to the pre-skew stream (dtype is part of the
            # Generator contract — don't change it)
            draw = rng.integers(0, self.num_nodes, size=self.batch_size,
                                dtype=np.int32)
        else:
            # inverse-CDF sampling of popularity ranks, mapped to indices
            u = rng.random(self.batch_size)
            ranks = np.searchsorted(self._cdf, u, side="right")
            draw = self._rank2idx[np.minimum(ranks, self.num_nodes - 1)]
        out = draw if self.ids is None else self.ids[draw]
        return out.astype(np.int32)


class EpochSeedStream:
    """Epoch-aware training seed stream: shuffled, without replacement.

    Each epoch is an independent permutation of ``ids`` (rng keyed by
    ``(seed, epoch)``) cut into fixed-size batches; ``drop_last`` keeps the
    batch shape static so the compiled train step never sees a ragged tail.
    ``batch(step)`` is a pure function of ``(seed, step)`` — the same
    restart-determinism contract as ``SeedStream`` — so a trainer resumed
    mid-epoch replays the exact remaining batches of that epoch.

    ``epoch_of(step)`` is the loader's epoch hook: when present on a seed
    source, ``MiniBatchLoader`` keys the sampler rng *and* the sampled-block
    cache by the epoch, so neighbor resampling stays stochastic across
    epochs (no stale block replay).
    """

    def __init__(self, ids: np.ndarray, batch_size: int, seed: int = 0,
                 drop_last: bool = True):
        self.ids = np.asarray(ids, dtype=np.int32)
        if self.ids.ndim != 1 or self.ids.size == 0:
            raise ValueError("ids must be a non-empty 1-D int array")
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        self.batch_size = min(batch_size, self.ids.size)
        self.seed = seed
        self.drop_last = drop_last
        n = self.ids.size
        self.batches_per_epoch = (n // self.batch_size if drop_last
                                  else -(-n // self.batch_size))
        self._perm_cache = (-1, None)   # (epoch, permutation) memo

    @property
    def num_ids(self) -> int:
        return int(self.ids.size)

    def epoch_of(self, step: int) -> int:
        return step // self.batches_per_epoch

    def steps_for(self, epochs: int) -> int:
        return epochs * self.batches_per_epoch

    def batch(self, step: int) -> np.ndarray:
        epoch, k = divmod(step, self.batches_per_epoch)
        if self._perm_cache[0] != epoch:
            # still a pure function of (seed, epoch): the memo only avoids
            # re-permuting the full id set for every batch of an epoch
            self._perm_cache = (epoch, np.random.default_rng(
                (self.seed, epoch)).permutation(self.ids.size))
        perm = self._perm_cache[1]
        lo = k * self.batch_size
        return self.ids[perm[lo:lo + self.batch_size]]


@dataclasses.dataclass
class MiniBatch:
    """Device-ready bundle for one sampled batch: per-hop graph tensors and
    kernel layouts, plus the gather maps that chain hops and restore the
    requested seed order."""

    step: int
    seq: BlockSequence
    tensors: List[GraphTensors]
    layouts: List[codegen.KernelLayouts]
    input_ids: jnp.ndarray          # [n_input] global IDs feeding hop 0
    dst_locals: List[jnp.ndarray]   # per hop: local rows of the out frontier
    seed_perm: jnp.ndarray          # final-frontier row of each seed
    # pre-gathered input features for this batch (a ``{"feature": [n, d]}``
    # pytree), attached by a loader wired to a ``repro.feats`` store: the
    # gather for batch k+1 is dispatched while batch k executes, so the
    # host->device row transfer rides the prefetch overlap. ``None`` means
    # the executor indexes the global table itself (pre-tiering behavior).
    # Executors DONATE these buffers — they are valid for one consumption.
    feats: Optional[dict] = None

    @property
    def num_hops(self) -> int:
        return len(self.tensors)


def build_minibatch(seq: BlockSequence, step: int = 0, tile: int = 128,
                    node_block: int = 128, bucket: bool = False,
                    layout_cache: Optional[LRUCache] = None,
                    layout_scope=None, shape_floors=None) -> MiniBatch:
    """Host-side assembly of a ``MiniBatch`` from a sampled ``BlockSequence``.

    With ``bucket=True`` (the serving fast path) each block graph, its
    kernel layouts, and every gather-index vector are padded to power-of-two
    buckets, so the compiled-shape set is small and repeated batches run
    from warm compilation caches. Padding is numerically inert: pad
    nodes/edges only feed pad rows, which the hop-chaining gathers never
    read.

    ``layout_cache`` (an ``LRUCache``) memoizes ``KernelLayouts`` by block
    signature, skipping the host-side NumPy layout passes for blocks seen
    before. ``layout_scope`` (any hashable, e.g. a partition id) namespaces
    the cache entries so callers sharing one cache across graph shards
    never replay each other's layouts.

    ``shape_floors`` (a ``bucketing.ShapeFloors``) additionally pads each
    hop up to the largest bucket previously seen for this seed count — the
    serving runtime's grow-only guarantee that one ladder rung converges
    to one compiled shape set instead of retracing on every fresh bucket
    combination.
    """
    graphs = [b.graph for b in seq.blocks]
    input_ids = seq.input_node_ids
    dst_locals = [b.dst_local for b in seq.blocks]
    if bucket:
        if shape_floors is not None:
            key = int(seq.seed_perm.shape[0])
            graphs = [shape_floors.pad_graph(key, i, g)
                      for i, g in enumerate(graphs)]
        else:
            graphs = [pad_block_graph(g) for g in graphs]
        input_ids = pad_index(input_ids, graphs[0].num_nodes)
        # hop l's output rows become hop l+1's (padded) node-feature rows;
        # the last hop only needs to cover the seed gather, so any stable
        # bucket works.
        dst_locals = [
            pad_index(d, graphs[i + 1].num_nodes if i + 1 < len(graphs)
                      else (shape_floors.pad_tail(key, d.shape[0])
                            if shape_floors is not None
                            else pow2ceil(d.shape[0])))
            for i, d in enumerate(dst_locals)
        ]

    def layouts_for(hop: int, g: HeteroGraph) -> codegen.KernelLayouts:
        # Layout-internal row buckets jitter with the edge distribution even
        # at pinned graph buckets, so the floors must reach into the layout
        # build too — and the cache key must carry the floor values, or a
        # pre-growth entry would replay stale shapes after a floor raise.
        rf = (shape_floors.layout_floors(int(seq.seed_perm.shape[0]), hop)
              if bucket and shape_floors is not None else None)
        if layout_cache is None:
            return codegen.build_kernel_layouts(
                g, tile=tile, node_block=node_block, bucket=bucket,
                row_floors=rf)
        ck = (layout_scope, block_signature(g, tile, node_block, bucket),
              None if rf is None else (hop, tuple(sorted(rf.items()))))
        kl = layout_cache.get(ck)
        if kl is None:
            kl = codegen.build_kernel_layouts(
                g, tile=tile, node_block=node_block, bucket=bucket,
                row_floors=rf)
            layout_cache.put(ck, kl)
        return kl

    return MiniBatch(
        step=step,
        seq=seq,
        tensors=[g.to_tensors() for g in graphs],
        layouts=[layouts_for(i, g) for i, g in enumerate(graphs)],
        input_ids=jnp.asarray(input_ids),
        dst_locals=[jnp.asarray(d) for d in dst_locals],
        seed_perm=jnp.asarray(seq.seed_perm),
    )


class _EndOfStream(Exception):
    """Internal: a callable seed source returned None — stream over."""


def _partition_token(partition):
    """Stable hashable identity of a graph partition (or shard thereof).

    Accepts ``None`` (unpartitioned), a ``repro.dist.GraphPartition``
    (identified by its shard bounds), a ``(GraphPartition, shard_index)``
    pair, or any hashable token the caller chooses."""
    if partition is None:
        return None
    if isinstance(partition, tuple) and len(partition) == 2:
        return (_partition_token(partition[0]), partition[1])
    bounds = getattr(partition, "bounds", None)
    if bounds is not None:
        return ("part", int(getattr(partition, "num_parts", 0)),
                np.asarray(bounds).tobytes())
    return partition


class MiniBatchLoader:
    """Background-thread prefetch of sampled mini-batches.

    ``seed_source`` is a ``SeedStream`` or any ``step -> np.ndarray``
    callable. Iteration yields ``MiniBatch`` in step order; with
    ``num_batches`` set the loader raises ``StopIteration`` afterwards. A
    *callable* source may also return ``None`` to end the stream early —
    the hook the online serving runtime uses to drain an unbounded loader
    on shutdown.

    Failure contract: an exception anywhere in the producer pipeline
    (seed source, sampler, layout build, feature gather) is re-raised in
    the consumer on its next ``__next__`` — after already-built batches —
    with the worker thread stopped and joined first; a worker that dies
    without managing to report is detected and surfaced as a
    ``RuntimeError`` instead of stalling the iterator forever.

    ``partition`` names the graph shard this loader samples from (a
    ``repro.dist.GraphPartition``, a ``(partition, shard)`` pair, or any
    hashable id): it becomes part of every block/layout cache key, so
    multiple shards sharing a process never replay each other's cached
    blocks.

    ``cache_blocks``/``cache_layouts`` give the two LRU capacities (0
    disables either). The sampled-block cache is keyed by
    ``(seeds, fanout, layout config, sampler epoch)``: for *serving* streams
    (no epoch) a repeated seed batch returns the block sampled at its first
    occurrence (re-stamped with the current step), trading per-request
    resampling noise for skipping the whole host pipeline. For *training*
    streams — any seed source exposing ``epoch_of(step)``, e.g.
    ``EpochSeedStream`` — the epoch is part of the key and also re-keys the
    sampler rng, so the same seed batch in a later epoch draws a fresh
    neighborhood instead of silently replaying a stale cached block
    (which would destroy neighbor-sampling stochasticity).
    """

    _SENTINEL = object()

    def __init__(
        self,
        sampler: FanoutSampler,
        seed_source: Union[SeedStream, EpochSeedStream,
                           Callable[[int], np.ndarray]],
        *,
        tile: int = 128,
        node_block: int = 128,
        bucket: bool = False,
        depth: int = 2,
        start_step: int = 0,
        num_batches: Optional[int] = None,
        cache_blocks: int = 0,
        cache_layouts: int = 0,
        partition=None,
        feature_store=None,
        shape_floors=None,
    ):
        self.sampler = sampler
        # serving's grow-only bucket floors (bucketing.ShapeFloors); host
        # pipeline only — the device sampler has its own bucket hysteresis
        self.shape_floors = shape_floors
        # a repro.feats store: the producer gathers each batch's input rows
        # and attaches them as mb.feats (single-writer contract — only this
        # loader's producer calls gather on it)
        self.feature_store = feature_store
        self._seeds_for = (seed_source.batch
                           if hasattr(seed_source, "batch") else seed_source)
        # training streams expose epoch_of(step); serving streams don't
        self._epoch_of = getattr(seed_source, "epoch_of", None)
        self.tile = tile
        self.node_block = node_block
        self.bucket = bucket
        self.num_batches = num_batches
        self.block_cache = LRUCache(cache_blocks, name="block_cache") \
            if cache_blocks else None
        self.layout_cache = LRUCache(cache_layouts, name="layout_cache") \
            if cache_layouts else None
        self._fanout_key = tuple(
            tuple(int(x) for x in f) for f in sampler.fanouts)
        # shard identity: loaders for different partitions of one graph may
        # share a process (and, via a shared LRUCache, each other's layout
        # cache) — the partition token keeps their cached blocks/layouts
        # from colliding on identical local seed ids
        self._partition_key = _partition_token(partition)
        # a DeviceSampler builds whole MiniBatches on device; everything else
        # goes through the host sample + build_minibatch pipeline
        self.mode = ("device" if hasattr(sampler, "sample_minibatch")
                     else "host")
        self.host_builds = 0     # batches built by the host NumPy pipeline
        self.device_builds = 0   # batches built by jit device programs
        self._start_step = start_step
        self._done = False
        if self.mode == "device":
            # threadless prefetch: a deque of already-dispatched batches
            self._depth = max(1, depth)
            self._next_step = start_step
            self._pending: collections.deque = collections.deque()
            self._thread = None
            return
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def cache_stats(self) -> dict:
        """Hit/miss counters of both loader caches (empty dict if disabled)."""
        out = {}
        if self.block_cache is not None:
            out["block_cache"] = self.block_cache.stats()
        if self.layout_cache is not None:
            out["layout_cache"] = self.layout_cache.stats()
        return out

    def build_stats(self) -> dict:
        """Which pipeline built the non-cached batches (the ``sample_native``
        CI gate asserts ``host_builds == 0`` in device mode), plus the
        per-cache hit *rates* (not just raw counters)."""
        out = {"mode": self.mode, "host_builds": self.host_builds,
               "device_builds": self.device_builds}
        if self.block_cache is not None:
            out["block_cache_hit_rate"] = self.block_cache.hit_rate
        if self.layout_cache is not None:
            out["layout_cache_hit_rate"] = self.layout_cache.hit_rate
        return out

    def _attach_feats(self, mb: MiniBatch, step: int) -> MiniBatch:
        """Gather this batch's input-feature rows through the store and
        attach them. Runs on the producer (thread or async-dispatch), so
        the host gather + transfer for batch k+1 overlaps batch k's
        compute. Cached batches are stored *without* feats: the cached
        store's state advances every batch, so each occurrence re-gathers
        (hot rows stay device-side in the cached store, making the
        re-gather cheap)."""
        if self.feature_store is None:
            return mb
        # stores normalize ids themselves: the device tier keeps them on
        # device (no sync); host/cached tiers pull them to host (the row
        # addresses are needed there — the unavoidable cost of the tier)
        feats = self.feature_store.gather(mb.input_ids, step=step)
        return dataclasses.replace(mb, feats=feats)

    def _cache_key(self, seeds: np.ndarray, epoch) -> tuple:
        return (seeds.tobytes(), self._fanout_key, self.tile,
                self.node_block, self.bucket, epoch, self._partition_key)

    def _build(self, step: int) -> MiniBatch:
        seeds = self._seeds_for(step)
        if seeds is None:   # callable sources may end the stream this way
            raise _EndOfStream
        epoch = self._epoch_of(step) if self._epoch_of is not None else None
        key = None
        if self.block_cache is not None:
            key = self._cache_key(seeds, epoch)
            mb = self.block_cache.get(key)
            if mb is not None:
                return self._attach_feats(
                    dataclasses.replace(mb, step=step), step)
        self.host_builds += 1
        with obs.span("sample", step=step):
            seq = self.sampler.sample(seeds, batch_index=step, epoch=epoch)
        with obs.span("layout", step=step):
            mb = build_minibatch(seq, step=step, tile=self.tile,
                                 node_block=self.node_block,
                                 bucket=self.bucket,
                                 layout_cache=self.layout_cache,
                                 layout_scope=self._partition_key,
                                 shape_floors=self.shape_floors)
        if self.block_cache is not None:
            self.block_cache.put(key, mb)   # cached without feats
        return self._attach_feats(mb, step)

    def _build_device(self, step: int) -> MiniBatch:
        seeds = self._seeds_for(step)
        if seeds is None:
            raise _EndOfStream
        epoch = self._epoch_of(step) if self._epoch_of is not None else None
        key = None
        if self.block_cache is not None:
            key = self._cache_key(seeds, epoch)
            mb = self.block_cache.get(key)
            if mb is not None:
                return self._attach_feats(
                    dataclasses.replace(mb, step=step), step)
        self.device_builds += 1
        mb = self.sampler.sample_minibatch(seeds, batch_index=step,
                                           epoch=epoch, step=step)
        if self.block_cache is not None:
            self.block_cache.put(key, mb)   # cached without feats
        return self._attach_feats(mb, step)

    def _pump(self) -> None:
        """Dispatch device builds until the prefetch window is full: JAX
        execution is asynchronous, so each build enqueues device work and
        returns — batch k+1 samples while the consumer executes batch k."""
        while len(self._pending) < self._depth:
            if (self.num_batches is not None and
                    self._next_step - self._start_step >= self.num_batches):
                return
            try:
                self._pending.append(self._build_device(self._next_step))
            except _EndOfStream:
                self.num_batches = self._next_step - self._start_step
                return
            self._next_step += 1

    def _fill(self):
        step = self._start_step
        item = None
        while not self._stop.is_set():
            if item is None:
                if (self.num_batches is not None
                        and step - self._start_step >= self.num_batches):
                    item = self._SENTINEL
                else:
                    try:
                        item = self._build(step)
                    except _EndOfStream:
                        item = self._SENTINEL
                    except BaseException as e:  # surface in the consumer
                        item = e
                    step += 1
            try:
                self.q.put(item, timeout=0.5)
            except queue.Full:
                continue
            if item is self._SENTINEL or isinstance(item, BaseException):
                break
            item = None

    def __iter__(self):
        return self

    def __next__(self) -> MiniBatch:
        if self._done:
            raise StopIteration
        if self.mode == "device":
            self._pump()
            if not self._pending:
                self._done = True
                raise StopIteration
            mb = self._pending.popleft()
            self._pump()   # dispatch the next batch before the caller executes
            return mb
        while True:
            try:
                item = self.q.get(timeout=0.5)
                break
            except queue.Empty:
                # a worker that died without enqueuing anything (it should
                # always enqueue its exception, but a daemon thread can be
                # torn down mid-put) must surface as an error, not as an
                # iterator that blocks forever
                if self._thread is not None and not self._thread.is_alive():
                    self._done = True
                    raise RuntimeError(
                        "MiniBatchLoader worker thread died without "
                        "reporting a batch or an exception") from None
        if item is self._SENTINEL:
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            # the producer failed on this batch: it enqueued the exception
            # and exited its loop — stop the worker cleanly, then re-raise
            # in the consumer instead of stalling the serving loop
            self._done = True
            self._stop.set()
            self._thread.join(timeout=2)
            raise item
        return item

    def close(self):
        if self.mode == "device":
            self._done = True
            self._pending.clear()
            return
        self._stop.set()
        # drain so a blocked producer can observe the stop flag
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2)
