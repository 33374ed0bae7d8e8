"""Device-native fanout sampler: sample -> layout -> execute without host
NumPy in the steady-state loop.

``DeviceSampler`` reproduces ``FanoutSampler``'s exact selection — both rank
candidate in-edges by the shared counter-based keys of
``sampler.edge_sample_keys`` over the shared destination-sorted candidate
positions, keyed by the same ``hop_base_key(seed, batch_index, hop, epoch)``
— but evaluates it as jit-compiled programs over a device-resident CSC
(``HeteroGraph.to_device_graph``), and builds each block's ``GraphTensors``
*and* ``KernelLayouts`` on device (``kernels/sampling_ops.py``). The
``MiniBatch`` it emits is a drop-in for the host loader's: same pytree
types, same hop chaining, same seed-order restoration.

Shape discipline (the retrace-freeness contract): every per-hop program is
compiled for a static (frontier bucket, fanout, count-bucket) tuple. Stage A
(selection) is shaped by the frontier bucket alone; the stage-B
(compaction + layout) bucket is *predicted*, never read back: each
``(hop, seed bucket, fanout)`` signature starts from its analytic worst
case (``fp * sum(k_eff)`` edges, capped by the graph — always correct), and
one non-blocking drain of past count vectors (``jax.Array.is_ready`` only,
never a blocking wait) shrinks the guess once to one power-of-two step above
the observed counts. The steady-state loop therefore issues **zero**
device->host syncs; ``count_syncs`` / ``bucket_overflows`` /
``bucket_shrinks`` pin that for the ``sample_native`` CI gate, alongside
``trace_count`` / ``cache_hits`` / ``cache_misses`` for retrace-freeness.
A shrunken guess that a later batch outgrows is detected by the same drain
(``bucket_overflows``) and reset to the worst case; the 2x headroom above
the observed counts makes that a monitored never-event in practice.

Prefetch overlap needs no thread: both stages are async-dispatched JAX
computations, so the loader simply dispatches batch k+1's sampling before
the consumer executes batch k — the two pipelines interleave as separate
streams of enqueued device work.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.graph import HeteroGraph
from repro.kernels import sampling_ops as SO
from repro.kernels.layout import pow2ceil
from repro.sampling.sampler import (FanoutSpec, hop_base_key,
                                    normalize_fanout)


@dataclasses.dataclass
class DeviceBlock:
    """Metadata summary of one device-sampled hop (execution order).

    The counts are the static bucket *capacities* (upper bounds on the real
    counts): the sync-free loop never reads the exact counts back, so the
    summary reports what was allocated, not what was filled. Real entries
    are identified in the tensors themselves by the sentinel pads."""

    num_src: int      # node bucket capacity (>= real nodes in the block)
    num_edges: int    # edge bucket capacity (>= real sampled edges)
    num_dst: int      # output-frontier capacity (exact for the seed hop)
    node_ids: jnp.ndarray   # [n_pad] sorted global ids, sentinel N pads


@dataclasses.dataclass
class DeviceBlockSequence:
    """Device-path stand-in for ``sampler.BlockSequence``: carries the seed
    bookkeeping the consumers need (label slicing, per-hop summaries) without
    materializing host ``Block``/``HeteroGraph`` objects."""

    blocks: List[DeviceBlock]
    seeds: np.ndarray       # requested seed IDs, order and dupes preserved
    num_nodes: int          # full-graph N (the pad sentinel)

    @property
    def num_hops(self) -> int:
        return len(self.blocks)

    def slice_labels(self, labels: np.ndarray) -> np.ndarray:
        """Labels aligned with the block forward's output (one row per
        requested seed, in request order) — same contract as the host."""
        return np.asarray(labels)[self.seeds]

    def describe(self) -> str:
        lines = [f"DeviceBlockSequence(seeds={len(self.seeds)})"]
        for i, b in enumerate(self.blocks):
            lines.append(f"  hop {i}: {b.num_src} nodes -> {b.num_dst} dst, "
                         f"{b.num_edges} edges (device)")
        return "\n".join(lines)


class DeviceSampler:
    """Jit-compiled fanout sampling + layout build over a device CSC.

    ``sample_minibatch`` is the whole device pipeline for one batch; the
    loader's ``backend="device"`` path calls it instead of
    ``FanoutSampler.sample`` + ``build_minibatch``.
    """

    def __init__(self, hg: HeteroGraph, fanouts: Sequence[FanoutSpec],
                 seed: int = 0, *, tile: int = 32, node_block: int = 32,
                 backend: str = "xla"):
        if not fanouts:
            raise ValueError("need at least one hop fanout")
        if tile & (tile - 1):
            raise ValueError("device sampling needs a power-of-two tile")
        if hg.num_edges == 0:
            raise ValueError("device sampling needs a graph with edges")
        self.hg = hg
        self.dg = hg.to_device_graph()
        self.fanouts = [normalize_fanout(f, hg.num_etypes) for f in fanouts]
        self.seed = seed
        self.tile = tile
        self.node_block = node_block
        # keys are Pallas-kernel-generated off the XLA backends' default;
        # selection/compaction are XLA sorts either way
        self.key_backend = "xla" if backend == "xla" else "pallas_interpret" \
            if backend == "pallas_interpret" else "pallas"
        self._k_eff = [SO.effective_fanouts(f, self.dg.max_bin)
                       for f in self.fanouts]
        self._jit = {}
        self.trace_count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.batches_sampled = 0
        # sync-free bucket speculation: per-(hop, frontier bucket, fanout)
        # stage-B bucket guesses, plus the queue of not-yet-inspected count
        # vectors (drained only when already resident on host)
        self._guess = {}          # sig -> (n_pad, e_pad, u_pad)
        self._shrunk = set()      # sigs whose guess already tightened once
        self._pending = collections.deque()  # (sig, used_buckets, counts)
        self.count_syncs = 0
        self.bucket_overflows = 0
        self.bucket_shrinks = 0

    @property
    def num_hops(self) -> int:
        return len(self.fanouts)

    # ------------------------------------------------------------------
    def _compiled(self, key, factory):
        """Explicit jit cache keyed by the static bucket tuple, with trace
        counting *inside* the traced function (the executor idiom): a cache
        hit that somehow retraced would still be counted."""
        fn = self._jit.get(key)
        if fn is None:
            self.cache_misses += 1
            inner = factory()

            def counted(*args, _inner=inner):
                self.trace_count += 1
                obs.metrics().counter("sampler_traces").inc()
                return _inner(*args)

            fn = jax.jit(counted)
            self._jit[key] = fn
        else:
            self.cache_hits += 1
        return fn

    def _bucket(self, count: int) -> int:
        return max(self.tile, pow2ceil(count + 1))

    def _worst_buckets(self, fp: int, k_eff) -> tuple:
        """Analytic stage-B buckets that can never overflow: ``fp`` frontier
        rows each select at most ``sum(k_eff)`` edges (capped by the graph's
        edge count), the union frontier adds at most one node per edge on
        top of the frontier itself (capped by N), and the unique
        (src, etype) pairs are at most the edges."""
        ksum = max(1, int(sum(k_eff)))
        e_w = min(self.hg.num_edges, fp * ksum)
        n_w = min(self.hg.num_nodes, fp + e_w)
        return (self._bucket(n_w), self._bucket(e_w), self._bucket(e_w))

    def drain(self, block: bool = False) -> None:
        """Inspect finished stage-A count vectors and tighten bucket
        guesses. Non-blocking by default — only counts already resident on
        the host (``is_ready``) are read, so the sampling loop stays
        sync-free. ``block=True`` waits for everything outstanding (a
        warmup barrier for tests; each forced wait counts as a
        ``count_syncs`` readback)."""
        while self._pending:
            sig, fp, used, counts = self._pending[0]
            if not counts.is_ready():
                if not block:
                    return
                self.count_syncs += 1
            self._pending.popleft()
            got = tuple(int(x) for x in np.asarray(counts))
            if any(c + 1 > b for c, b in zip(got, used)):
                # a shrunken bucket truncated this batch: report it and
                # fall back to the always-correct worst case
                self.bucket_overflows += 1
                self._guess[sig] = self._worst_buckets(fp, sig[2])
                self._shrunk.discard(sig)
                continue
            if sig in self._shrunk:
                continue
            worst = self._worst_buckets(fp, sig[2])
            # one pow2 step of headroom above the first observed counts;
            # shrink once per signature so steady state never re-buckets
            new = tuple(min(w, 2 * self._bucket(c))
                        for c, w in zip(got, worst))
            if new != self._guess.get(sig, worst):
                self._guess[sig] = new
                self.bucket_shrinks += 1
            self._shrunk.add(sig)

    # ------------------------------------------------------------------
    def sample_minibatch(self, seeds: np.ndarray, batch_index: int = 0,
                         epoch: Optional[int] = None, step: int = 0):
        """Sample + build one device-resident ``MiniBatch``.

        Randomness is keyed identically to the host sampler —
        ``hop_base_key(seed, batch_index, hop, epoch)`` — so the two paths
        select the same edge multisets for the same stream position.
        """
        from repro.sampling.loader import MiniBatch  # local: avoid cycle

        seeds = np.asarray(seeds, dtype=np.int32)
        if seeds.ndim != 1 or seeds.size == 0:
            raise ValueError("seeds must be a non-empty 1-D int array")
        if seeds.min() < 0 or seeds.max() >= self.hg.num_nodes:
            raise ValueError("seed node id out of range")
        dg = self.dg
        nhops = self.num_hops
        b = int(seeds.shape[0])
        f0 = pow2ceil(b)

        prep = self._compiled(
            ("prep", b, f0),
            lambda: SO.make_prep_seeds(dg.num_nodes, f0))
        frontier, seed_perm = prep(jnp.asarray(seeds))

        self.drain()      # non-blocking: fold in any finished count vectors

        hops = []         # sampling order (outermost first)
        num_dst = [None] * nhops
        prev_cap = None   # node capacity of the previous hop's union
        for hop in range(nhops):
            k_eff = self._k_eff[nhops - 1 - hop]
            kmax = max(1, max(k_eff))
            fp = int(frontier.shape[0])
            base = jnp.asarray(
                hop_base_key(self.seed, int(batch_index), hop, epoch))
            with obs.span("sample_device", step=step, hop=hop):
                fn_a = self._compiled(
                    ("A", fp, k_eff, self.key_backend),
                    lambda k_eff=k_eff, fp=fp: SO.make_sample_hop(
                        dg, k_eff, fp, self.key_backend))
                union, sel_src, sel_valid, counts = fn_a(
                    dg.csc_indptr, dg.csc_src, frontier, base)
            # sync-free bucket pick: use the signature's current guess
            # (worst case until a drained count vector tightened it) and
            # queue the counts for a later non-blocking inspection. The
            # signature carries the *seed* bucket, not the frontier bucket:
            # real counts are invariant to padding, so a guess learned
            # before an earlier hop shrank its frontier stays valid after.
            sig = (hop, f0, k_eff)
            guess = self._guess.setdefault(
                sig, self._worst_buckets(fp, k_eff))
            n_pad, e_pad, u_pad = guess
            self._pending.append((sig, fp, guess, counts))
            with obs.span("layout_device", step=step, hop=hop):
                fn_b = self._compiled(
                    ("B", fp, kmax, n_pad, e_pad, u_pad),
                    lambda fp=fp, kmax=kmax, n_pad=n_pad, e_pad=e_pad,
                    u_pad=u_pad: SO.make_build_block(
                        dg, fp, kmax, n_pad, e_pad, u_pad,
                        self.tile, self.node_block))
                gt, kl, node_ids, dst_local, input_gather = fn_b(
                    union, sel_src, sel_valid, frontier, dg.node_type)
            hops.append(dict(gt=gt, kl=kl, node_ids=node_ids,
                             dst_local=dst_local, input_gather=input_gather,
                             num_src=n_pad, num_edges=e_pad))
            num_dst[hop] = prev_cap if prev_cap is not None else None
            prev_cap = n_pad
            frontier = node_ids

        # execution order: innermost (last sampled) hop first
        hops.reverse()
        num_dst.reverse()
        blocks = [DeviceBlock(num_src=h["num_src"], num_edges=h["num_edges"],
                              num_dst=(d if d is not None
                                       else int(np.unique(seeds).size)),
                              node_ids=h["node_ids"])
                  for h, d in zip(hops, num_dst)]
        seq = DeviceBlockSequence(blocks=blocks, seeds=seeds,
                                  num_nodes=self.hg.num_nodes)
        self.batches_sampled += 1
        return MiniBatch(
            step=step,
            seq=seq,
            tensors=[h["gt"] for h in hops],
            layouts=[h["kl"] for h in hops],
            input_ids=hops[0]["input_gather"],
            dst_locals=[h["dst_local"] for h in hops],
            seed_perm=seed_perm,
        )

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "batches_sampled": self.batches_sampled,
            "trace_count": self.trace_count,
            "jit_cache_hits": self.cache_hits,
            "jit_cache_misses": self.cache_misses,
            "compiled_programs": len(self._jit),
            "count_syncs": self.count_syncs,
            "bucket_overflows": self.bucket_overflows,
            "bucket_shrinks": self.bucket_shrinks,
            "pending_counts": len(self._pending),
        }
