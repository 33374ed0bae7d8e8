"""Tiered node-feature storage (ISSUE 9 tentpole).

Where the ``[N, dim]`` node-feature table lives decides the memory ceiling
of the whole stack: with the table device-resident (the only mode before
this package existed), feature scale — not graph-structure scale — is the
binding limit, undermining the paper's headline "never OOMs" claim for
OGB-size inputs. This package makes storage *tiered*:

* ``DeviceFeatureStore`` — the classic layout: the full table lives on
  device, per-batch input rows are a device-side gather. Fastest when it
  fits; the baseline the other tiers must match bitwise.

* ``HostFeatureStore`` — the table lives in **per-ntype host-resident
  arrays** (page-locked/pinned on real accelerator runtimes; on the CPU
  backend they are plain aligned NumPy arrays — the follow-up for real
  GPUs is UVA zero-copy gather, see ROADMAP). Only the sampled blocks'
  input rows are gathered per batch and shipped to device; the loader
  dispatches the gather for batch k+1 while batch k executes, so the
  transfer rides the existing prefetch overlap.

* ``CachedFeatureStore`` — fronts the host tier with a **fixed-budget
  device hot-row cache**: one slot slab ``[S, dim]`` on device,
  partitioned per ntype (``slot_ptr``, mirroring ``ntype_ptr``), with
  host-side index translation and CLOCK eviction decided on host from the
  sampled row ids. Hits never leave the device: the per-batch features
  are produced by one jitted insert+gather program whose cache state is
  threaded through as a **donated** input, so the slab is updated in
  place and a fully-hot batch performs zero host feature work.

All three backends return bitwise-identical feature rows (the bits only
ever move; they are never recomputed), which is what lets every execution
mode — serve, train, device-sampled, distributed — switch tiers freely.

Observability: every gather runs under a ``feature_gather`` span;
``feature_cache_{hits,misses,evictions}`` counters,
``feature_bytes_moved`` (per-gather gauge) and
``feature_bytes_moved_total`` / ``feature_host_gathers`` counters land in
the metrics registry when enabled. The plain integer attributes on the
stores remain the always-on source of truth, same contract as the loader
LRUs.

Threading: a store is **single-writer** — exactly one ``MiniBatchLoader``
producer (or the driver thread) may call ``gather``; read-only surfaces
(``stats``, ``device_bytes``) are safe anywhere.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.graph import HeteroGraph
from repro.kernels.layout import pow2ceil


def split_budget(graph: HeteroGraph, budget: int,
                 weights: Optional[Sequence[float]] = None) -> np.ndarray:
    """Split ``budget`` cache rows across ntypes: proportional to
    ``weights`` (default: ntype populations), capped at each ntype's table
    size (slots beyond a table's row count can never hold a distinct row),
    with the remainder redistributed to uncapped types by weight.

    Returns per-ntype slot counts ``[T]`` summing to
    ``min(budget, num_nodes)``; a type can end up with zero slots (all its
    rows then ship uncached — correct, just never hot).
    """
    sizes = np.diff(graph.ntype_ptr).astype(np.int64)
    budget = int(min(max(0, budget), sizes.sum()))
    w = np.asarray(weights if weights is not None else sizes, np.float64)
    if w.shape != sizes.shape:
        raise ValueError(f"need {len(sizes)} weights, got {w.shape}")
    w = np.maximum(w, 0.0)
    slots = np.zeros(len(sizes), dtype=np.int64)
    remaining = budget
    free = w > 0
    # iterate: proportional assignment, cap at table size, redistribute
    while remaining > 0 and free.any() and w[free].sum() > 0:
        share = w * free / w[free].sum() * remaining
        add = np.minimum(np.floor(share).astype(np.int64), sizes - slots)
        if add.sum() == 0:  # round the largest fractional shares upward
            order = np.argsort(-share)
            for t in order:
                if remaining <= 0:
                    break
                if free[t] and slots[t] < sizes[t]:
                    slots[t] += 1
                    remaining -= 1
            break
        slots += add
        remaining -= int(add.sum())
        free = free & (slots < sizes)
    return slots.astype(np.int64)


class FeatureStore:
    """Protocol + shared host-side machinery for the three tiers.

    The surface every consumer codes against:

    * ``gather(ids, step=None) -> {"feature": jnp [n, dim]}`` — device-
      resident input rows for one batch (the executor feature pytree);
    * ``host_rows(ids) -> np [n, dim]`` — host-side row gather with no
      device involvement (the distributed slab builder reads through this,
      so shards never need the full table on device);
    * ``full_table() -> jnp [N, dim]`` — the whole table device-resident
      (full-graph eval/parity paths only; defeats tiering by design);
    * ``device_bytes()`` — persistent device bytes attributable to the
      store (the OOM-avoidance gate compares this against the full-table
      footprint).
    """

    kind = "base"

    def __init__(self, feats, graph: HeteroGraph):
        host = np.asarray(feats)
        if host.ndim != 2 or host.shape[0] != graph.num_nodes:
            raise ValueError(
                f"feature table must be [num_nodes={graph.num_nodes}, dim]; "
                f"got {host.shape}")
        self.graph = graph
        self.dim = int(host.shape[1])
        self.dtype = host.dtype
        self.itemsize = int(host.dtype.itemsize)
        self.num_rows = int(host.shape[0])
        self._host = np.ascontiguousarray(host)
        self.bytes_moved = 0
        self.rows_moved = 0
        self.host_gathers = 0   # batches that touched the host tables

    # -- protocol -------------------------------------------------------
    def gather(self, ids, step: Optional[int] = None) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def host_rows(self, ids) -> np.ndarray:
        """Host gather of global rows (no device work)."""
        return self._host[np.asarray(ids)]

    def full_table(self) -> jnp.ndarray:
        """The entire table on device — full-graph paths only."""
        return jnp.asarray(self._host)

    def device_bytes(self) -> int:
        return 0

    @property
    def table_bytes(self) -> int:
        """Footprint of the full table — the bound tiering must beat."""
        return self.num_rows * self.dim * self.itemsize

    def stats(self) -> dict:
        return {"kind": self.kind,
                "rows_moved": self.rows_moved,
                "bytes_moved": self.bytes_moved,
                "host_gathers": self.host_gathers,
                "device_bytes": self.device_bytes(),
                "table_bytes": self.table_bytes}

    # -- shared accounting ---------------------------------------------
    def _account_moved(self, rows: int) -> None:
        nbytes = rows * self.dim * self.itemsize
        self.rows_moved += rows
        self.bytes_moved += nbytes
        m = obs.metrics()
        m.gauge("feature_bytes_moved", store=self.kind).set(nbytes)
        m.counter("feature_bytes_moved_total", store=self.kind).inc(nbytes)


class DeviceFeatureStore(FeatureStore):
    """Today's behavior: full table device-resident, gather on device."""

    kind = "device"

    def __init__(self, feats, graph: HeteroGraph):
        super().__init__(feats, graph)
        self._table = jnp.asarray(self._host)
        # the one-time upload is the whole table
        self._account_moved(self.num_rows)

    def gather(self, ids, step=None) -> Dict[str, jnp.ndarray]:
        with obs.span("feature_gather", store=self.kind, step=step):
            return {"feature": self._table[jnp.asarray(ids)]}

    def full_table(self) -> jnp.ndarray:
        return self._table

    def device_bytes(self) -> int:
        return self.table_bytes


class HostFeatureStore(FeatureStore):
    """Host-resident tier: per-ntype host tables, block-row gather.

    ``tables[t]`` holds ntype ``t``'s rows (global rows
    ``ntype_ptr[t]:ntype_ptr[t+1]``) as an independent contiguous array —
    the layout a pinned-memory runtime registers per table. The gather
    translates global ids to (ntype, local row) through ``ntype_ptr``,
    reads host-side, and ships exactly the batch's rows; dispatch is
    asynchronous (``jax.device_put`` returns immediately), so calls made
    from the loader's producer overlap the consumer's compute.
    """

    kind = "host"

    def __init__(self, feats, graph: HeteroGraph):
        super().__init__(feats, graph)
        p = graph.ntype_ptr
        self.tables: List[np.ndarray] = [
            np.ascontiguousarray(self._host[int(p[t]):int(p[t + 1])])
            for t in range(graph.num_ntypes)]

    def host_rows(self, ids) -> np.ndarray:
        ids = np.asarray(ids, dtype=np.int64)
        ptr = self.graph.ntype_ptr.astype(np.int64)
        t = np.searchsorted(ptr, ids, side="right") - 1
        out = np.empty((ids.shape[0], self.dim), dtype=self.dtype)
        for tt in np.unique(t):
            m = t == tt
            out[m] = self.tables[int(tt)][ids[m] - ptr[int(tt)]]
        return out

    def gather(self, ids, step=None) -> Dict[str, jnp.ndarray]:
        ids = np.asarray(ids)
        with obs.span("feature_gather", store=self.kind, step=step):
            rows = self.host_rows(ids)
            self.host_gathers += 1
            self._account_moved(int(ids.shape[0]))
            obs.metrics().counter("feature_host_gathers",
                                  store=self.kind).inc()
            return {"feature": jax.device_put(rows)}


class CachedFeatureStore(HostFeatureStore):
    """Host tier fronted by a fixed-budget device hot-row cache.

    Device state is one slot slab ``slots [S, dim]`` partitioned per ntype
    by ``slot_ptr`` (ntype ``t`` owns slots ``slot_ptr[t]:slot_ptr[t+1]``).
    Host state is the index translation (``gid -> slot`` map, per-slot
    resident gid, CLOCK reference bits, per-ntype clock hands). Per batch:

    1. distinct requested rows are split into hits (already resident) and
       misses; CLOCK picks a victim slot for each miss *within its ntype's
       partition*, never evicting a slot this batch also reads (resident
       rows are pinned for the batch). Misses that find no victim
       (distinct batch rows exceed the partition) **overflow**: they ship
       to device for this batch but are not inserted.
    2. the miss rows are host-gathered and shipped (padded to a
       power-of-two bucket so the compiled program set stays fixed), and
       one jitted program scatters them into their slots (pad/overflow
       rows carry slot index ``S`` and drop) and gathers the batch's
       ``[n, dim]`` features from ``concat(slots, shipped)`` — cache hits
       therefore never leave the device. ``slots`` is donated: the slab
       updates in place, and state is
       threaded functionally (``self.slots`` is rebound to the program's
       output every batch).
    3. a fully-hot batch (zero misses) runs a read-only gather program:
       no host rows touched, no transfer, no slab write.

    Eviction is decided entirely on host from the sampled row ids, so a
    fixed seed stream yields a bit-reproducible cache state trajectory.
    All host bookkeeping is vectorized — the id -> slot map is an int32
    array over the node population (4 B/node host memory, small next to
    the >= dim*4 B/node feature row itself) and victim selection is one
    batched CLOCK sweep per ntype — so the per-batch host cost is a few
    NumPy passes over the batch, not a Python loop over rows.
    """

    kind = "cached"

    def __init__(self, feats, graph: HeteroGraph, budget: int,
                 split: Optional[Sequence[int]] = None,
                 miss_bucket_min: int = 8):
        super().__init__(feats, graph)
        per_ntype = (np.asarray(split, np.int64) if split is not None
                     else split_budget(graph, budget))
        if per_ntype.shape != (graph.num_ntypes,):
            raise ValueError(
                f"split needs {graph.num_ntypes} entries, got {per_ntype}")
        sizes = np.diff(graph.ntype_ptr)
        if (per_ntype > sizes).any():
            raise ValueError("per-ntype slots exceed the ntype's table size")
        self.slot_ptr = np.zeros(graph.num_ntypes + 1, dtype=np.int64)
        np.cumsum(per_ntype, out=self.slot_ptr[1:])
        self.capacity = int(self.slot_ptr[-1])
        self.miss_bucket_min = int(miss_bucket_min)
        # device state: the slab (zeros until rows are inserted)
        self.slots = jnp.zeros((max(self.capacity, 1), self.dim),
                               dtype=self.dtype)
        # host state: index translation + CLOCK metadata
        self._slot_gid = np.full(max(self.capacity, 1), -1, dtype=np.int64)
        self._ref = np.zeros(max(self.capacity, 1), dtype=bool)
        self._hand = np.zeros(graph.num_ntypes, dtype=np.int64)
        self._gid2slot = np.full(self.num_rows, -1, dtype=np.int32)
        # counters (distinct requested rows per batch)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.overflows = 0
        self.trace_count = 0   # (re)traces of the two gather programs
        self._insert_fn = jax.jit(self._traced_insert_gather,
                                  donate_argnums=(0,))
        self._hot_fn = jax.jit(self._traced_hot_gather)
        self._warmed: set = set()   # idx lengths whose programs are built

    # -- device programs ------------------------------------------------
    def _traced_insert_gather(self, slots, miss, ins, idx):
        # runs at trace time only (host): counts actual (re)traces so the
        # zero-retrace-after-warmup invariant is testable, like executors
        self.trace_count += 1
        obs.metrics().counter("feature_gather_traces").inc()
        slots = slots.at[ins].set(miss, mode="drop")
        # logical read source is concat(slots, miss)[idx]; materializing
        # that concat would copy the whole slab per batch, so read the two
        # halves separately (2 x [n, dim] gathers) and select
        S = slots.shape[0]
        in_slab = idx < S
        from_slab = slots[jnp.minimum(idx, S - 1)]
        from_ship = miss[jnp.clip(idx - S, 0, miss.shape[0] - 1)]
        return slots, jnp.where(in_slab[:, None], from_slab, from_ship)

    def _traced_hot_gather(self, slots, idx):
        self.trace_count += 1
        obs.metrics().counter("feature_gather_traces").inc()
        return slots[idx]

    def _prewarm(self, n_idx: int) -> None:
        """Compile the whole program set for batches of ``n_idx`` input
        rows up front: the hot gather plus every pow2 miss bucket up to
        ``n_idx``. Miss counts *shrink* as the cache warms, so without
        this, first-touch compiles of smaller buckets would show up as
        steady-state retraces. The warmup inserts nothing (every scatter
        index is the out-of-range drop slot), so cache state is untouched."""
        if n_idx in self._warmed:
            return
        self._warmed.add(n_idx)
        idx = jnp.zeros(n_idx, jnp.int32)
        self._hot_fn(self.slots, idx)
        S = int(self.slots.shape[0])
        mb = self.miss_bucket_min
        cap = max(pow2ceil(max(n_idx, 1)), self.miss_bucket_min)
        while mb <= cap:
            rows = jnp.zeros((mb, self.dim), self.dtype)
            ins = jnp.full((mb,), S, jnp.int32)      # all rows dropped
            self.slots, _ = self._insert_fn(self.slots, rows, ins, idx)
            mb *= 2

    # -- CLOCK eviction (host, one vectorized sweep per ntype) ---------
    def _pick_victims(self, t: int, k: int, pinned: np.ndarray) -> np.ndarray:
        """Up to ``k`` evictable slots in ntype ``t``'s partition, batch-
        CLOCK order: starting at the hand, unpinned-and-unreferenced slots
        first; if those run short the sweep dips into referenced slots
        (their second chance — the sweep clears their bits). Pinned slots
        (resident rows this batch reads) are never victims; fewer than
        ``k`` returned means the remainder overflows."""
        lo, hi = int(self.slot_ptr[t]), int(self.slot_ptr[t + 1])
        n = hi - lo
        if n == 0 or k <= 0:
            return np.empty(0, dtype=np.int64)
        order = lo + (int(self._hand[t]) + np.arange(n)) % n
        free = order[~pinned[order]]
        unref = free[~self._ref[free]]
        if unref.shape[0] >= k:
            victims = unref[:k]
        else:
            refd = free[self._ref[free]]
            self._ref[refd] = False      # swept past: second chance spent
            victims = np.concatenate([unref, refd])[:k]
        self._hand[t] = (int(self._hand[t]) + victims.shape[0]) % n
        return victims

    # -- the batch gather ----------------------------------------------
    def gather(self, ids, step=None) -> Dict[str, jnp.ndarray]:
        ids = np.asarray(ids)
        with obs.span("feature_gather", store=self.kind, step=step):
            return {"feature": self._gather_impl(ids)}

    def _gather_impl(self, ids: np.ndarray) -> jnp.ndarray:
        ptr = self.graph.ntype_ptr.astype(np.int64)
        uniq, inv = np.unique(ids.astype(np.int64), return_inverse=True)
        m = obs.metrics()
        self._prewarm(int(ids.shape[0]))

        slot_of = self._gid2slot[uniq].astype(np.int64)
        resident = slot_of >= 0
        hit_slots = slot_of[resident]
        miss_gids = uniq[~resident]
        self._ref[hit_slots] = True
        n_hit = int(resident.sum())
        n_miss = int(miss_gids.shape[0])
        self.hits += n_hit
        m.counter("feature_cache_hits").inc(n_hit)
        self.misses += n_miss
        m.counter("feature_cache_misses").inc(n_miss)

        S = int(self.slots.shape[0])
        if n_miss == 0:
            # fully hot: read-only slab gather, zero host feature work.
            # The int32 cast happens in NumPy and the array is handed to
            # the jitted call as-is: jit's argument-transfer path is far
            # cheaper than an eager device_put + dtype convert per batch.
            return self._hot_fn(self.slots, slot_of[inv].astype(np.int32))

        # victim assignment: one batched CLOCK sweep per ntype, in
        # ascending (ntype, gid) order — fully deterministic. Resident
        # rows this batch reads are pinned.
        pinned = np.zeros(S, dtype=bool)
        pinned[hit_slots] = True
        t_of = np.searchsorted(ptr, miss_gids, side="right") - 1
        ins_gids: List[np.ndarray] = []
        ins_slots: List[np.ndarray] = []
        over_gids: List[np.ndarray] = []
        n_evict = 0
        for t in np.unique(t_of):
            gids_t = miss_gids[t_of == t]     # sorted (uniq is sorted)
            victims = self._pick_victims(int(t), gids_t.shape[0], pinned)
            k = victims.shape[0]
            take = gids_t[:k]
            old = self._slot_gid[victims]
            live = old >= 0
            self._gid2slot[old[live]] = -1
            n_evict += int(live.sum())
            self._slot_gid[victims] = take
            self._gid2slot[take] = victims
            self._ref[victims] = True
            pinned[victims] = True            # this batch now reads them
            ins_gids.append(take)
            ins_slots.append(victims)
            if k < gids_t.shape[0]:           # overflow: ship uninserted
                over_gids.append(gids_t[k:])
        self.evictions += n_evict
        m.counter("feature_cache_evictions").inc(n_evict)

        inserted = np.concatenate(ins_gids) if ins_gids else \
            np.empty(0, dtype=np.int64)
        inserted_slots = np.concatenate(ins_slots) if ins_slots else \
            np.empty(0, dtype=np.int64)
        overflow = np.concatenate(over_gids) if over_gids else \
            np.empty(0, dtype=np.int64)
        n_over = int(overflow.shape[0])
        self.overflows += n_over
        if n_over:
            m.counter("feature_cache_overflows").inc(n_over)

        # per-distinct-row read source: cache slot for hits and freshly
        # inserted misses (the hit path and warm path share one compiled
        # access pattern), S + k for the k-th shipped overflow row
        uniq_read = self._gid2slot[uniq].astype(np.int64)
        if n_over:
            # shipped order: inserted misses first, overflow rows after
            pos = np.searchsorted(uniq, overflow)
            uniq_read[pos] = S + inserted.shape[0] + np.arange(n_over)
        shipped = np.concatenate([inserted, overflow])

        mb = max(pow2ceil(shipped.shape[0]), self.miss_bucket_min)
        rows = np.zeros((mb, self.dim), dtype=self.dtype)
        rows[: shipped.shape[0]] = self.host_rows(shipped)
        ins = np.full(mb, S, dtype=np.int64)   # S = out-of-range => dropped
        ins[: inserted.shape[0]] = inserted_slots

        self.host_gathers += 1
        m.counter("feature_host_gathers", store=self.kind).inc()
        self._account_moved(int(shipped.shape[0]))
        # NumPy operands go to the jitted call untouched — its transfer
        # path is one batched copy, vs ~3 dispatched device_puts eagerly
        self.slots, out = self._insert_fn(
            self.slots, rows, ins.astype(np.int32),
            uniq_read[inv].astype(np.int32))
        return out

    # -- reporting ------------------------------------------------------
    def device_bytes(self) -> int:
        return int(self.slots.shape[0]) * self.dim * self.itemsize

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        out = super().stats()
        out.update(hits=self.hits, misses=self.misses,
                   evictions=self.evictions, overflows=self.overflows,
                   hit_rate=self.hit_rate, capacity=self.capacity,
                   trace_count=self.trace_count,
                   slot_ptr=self.slot_ptr.tolist())
        g = obs.metrics().gauge("feature_cache_hit_rate")
        g.set(self.hit_rate)
        obs.metrics().gauge("feature_device_bytes").set(self.device_bytes())
        return out


def make_feature_store(feats, graph: HeteroGraph, kind: str = "device",
                       budget: Optional[int] = None,
                       split: Optional[Sequence[int]] = None) -> FeatureStore:
    """Build a feature store. ``kind`` in {"device", "host", "cached"};
    ``budget`` (cached only) is the device hot-row count, default one
    quarter of the table; ``split`` overrides the per-ntype slot split
    (e.g. the measured decision from ``tune.feature_budget``)."""
    if kind == "device":
        return DeviceFeatureStore(feats, graph)
    if kind == "host":
        return HostFeatureStore(feats, graph)
    if kind == "cached":
        if budget is None:
            budget = max(1, graph.num_nodes // 4)
        return CachedFeatureStore(feats, graph, budget=budget, split=split)
    raise ValueError(f"feature_store={kind!r}; pick device/host/cached")
