"""Cross-shard batch assembly for data-parallel execution.

``ShardedBatcher`` turns one request batch of seed nodes into a
``ShardedMiniBatch``: every per-hop array is stacked to a leading ``[P]``
shard axis so a single ``shard_map``-ped callable can run all shards'
block forwards at once. Three problems are solved on the host, once per
batch, so the compiled step needs **zero** host round-trips:

1. **Seed routing** — each seed goes to its owner shard, in request order;
   each shard's slice is padded to a common power-of-two ``b_max`` with a
   valid owned node (selection per (dst, etype) bin is independent of the
   rest of the batch, so pad seeds never disturb real selections). A
   ``route`` gather index maps request position -> (shard, slot), which the
   executor uses to restore request order from the gathered outputs.

2. **Common buckets** — shards sample different block sizes, but stacking
   needs identical shapes. Per hop, every shard's block is padded to the
   max bucket over shards (``common_block_targets``). The target
   computation is two-pass because raising the unique-pair bucket spends
   extra pad edges/nodes (see ``bucketing.pad_block_graph``).

3. **Fixed-capacity layouts** — ``codegen.build_kernel_layouts`` composes
   gather rows *before* bucket growth, so its row counts depend on block
   content. ``build_fixed_layouts`` instead grows every tile layout to the
   worst-case capacity implied by the (already common) graph buckets —
   ``sum_r ceil(seg_r/tile)*tile <= roundup(total) + groups*tile`` — and
   only then composes the gather rows, making all layout shapes a pure
   function of the bucket sizes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np

from repro.core import codegen
from repro.core.graph import GraphTensors, HeteroGraph
from repro.kernels import layout as L
from repro.kernels import ops as K
from repro.kernels.layout import pow2ceil
from repro.sampling.bucketing import pad_block_graph, pad_index
from repro.sampling.loader import LRUCache, block_signature
from repro.sampling.sampler import FanoutSpec
from repro.dist.partition import GraphPartition
from repro.dist.sampler import ShardedSampler


def common_block_targets(graphs: Sequence[HeteroGraph]) -> tuple:
    """Smallest common ``(n, e, u)`` power-of-two buckets that every graph
    in ``graphs`` can be padded to *exactly* by ``pad_block_graph``.

    Two-pass: the unique-pair target is fixed first because raising it
    costs each graph ``u_t - u_s`` extra pad edges (one per distinct pad
    pair) and ``ceil((u_t - u_s)/R)`` extra pad source nodes, which feed
    into the edge/node targets."""
    num_r = graphs[0].num_etypes
    u_t = max(pow2ceil(g.num_unique + 1) for g in graphs)
    e_t = max(pow2ceil(g.num_edges + (u_t - g.num_unique)) for g in graphs)
    n_t = max(
        pow2ceil(g.num_nodes + max(1, -(-(u_t - g.num_unique) // num_r)))
        for g in graphs)
    return n_t, e_t, u_t


def build_fixed_layouts(hg: HeteroGraph, tile: int = 128,
                        node_block: int = 128) -> codegen.KernelLayouts:
    """``KernelLayouts`` whose every array shape depends only on the graph's
    bucket sizes ``(num_nodes, num_edges, num_unique)`` plus the static
    type/tile counts — not on how edges distribute over segments/blocks.

    Each tile layout is grown to its worst case *before* the gather rows
    are composed (``codegen.build_kernel_layouts`` composes first, so its
    shapes are content-dependent and would not stack across shards)."""
    if tile & (tile - 1):
        raise ValueError("fixed layouts need a power-of-two tile")

    def up(x: int) -> int:
        return -(-x // tile) * tile

    num_r, num_t = hg.num_etypes, hg.num_ntypes
    edge_ps = L.pad_segments_rows(
        L.pad_segments(hg.etype_ptr, tile), up(hg.num_edges) + num_r * tile)
    unique_ps = L.pad_segments_rows(
        L.pad_segments(hg.unique_etype_ptr, tile),
        up(hg.num_unique) + num_r * tile)
    node_ps = L.pad_segments_rows(
        L.pad_segments(hg.ntype_ptr, tile), up(hg.num_nodes) + num_t * tile)
    nb = -(-hg.num_nodes // node_block)
    # message-row blocks, each at most one tile of padding
    rev_nb = -(-hg.num_unique // L.msg_row_block(hg.num_edges,
                                                   hg.num_unique, tile))
    bc = L.pad_blocked_csr(
        L.block_csr(hg.dst_ptr, edge_tile=tile, node_block=node_block),
        up(hg.num_edges) + nb * tile)
    return codegen.KernelLayouts(
        edge_seg=K.padded_segments_dev(edge_ps),
        unique_seg=K.padded_segments_dev(unique_ps),
        node_seg=K.padded_segments_dev(node_ps),
        blocked=K.blocked_csr_dev(bc, hg.perm_dst, hg.edge_to_unique),
        edge_src_rows=jnp.asarray(L.compose_gather_rows(edge_ps, hg.src)),
        edge_dst_rows=jnp.asarray(L.compose_gather_rows(edge_ps, hg.dst)),
        unique_src_rows=jnp.asarray(
            L.compose_gather_rows(unique_ps, hg.unique_src)),
        dst_deg=jnp.asarray(np.diff(hg.dst_ptr).astype(np.float32)),
        msg_rev=K.msg_rev_dev(hg.edge_to_unique, hg.num_unique, tile,
                              target_edges=up(hg.num_edges) + tile * rev_nb),
    )


def stack_pytrees(trees):
    """Stack a list of structurally identical pytrees leaf-wise along a new
    leading axis (the shard axis)."""
    return jtu.tree_map(lambda *xs: jnp.stack(xs), *trees)


@dataclasses.dataclass
class ShardedMiniBatch:
    """Device-ready bundle for one request batch across all ``P`` shards.

    Every jnp field has a leading shard axis ``[P, ...]`` (except ``route``,
    which lives in request space); static shapes are common across shards by
    construction, so ``shard_map`` can split the shard axis over devices."""

    step: int
    seeds: np.ndarray               # [B] requested seed nodes (global ids)
    shard_seeds: np.ndarray         # [P, b_max] routed + padded seed slices
    tensors: List[GraphTensors]     # per hop, leaves [P, ...]
    layouts: List[codegen.KernelLayouts]  # per hop, leaves [P, ...]
    dst_locals: List[jnp.ndarray]   # per hop [P, rows]
    seed_perm: jnp.ndarray          # [P, b_max] final-frontier row per slot
    owner_rows: jnp.ndarray         # [P, n_in] owner shard of hop-0 inputs
    local_rows: jnp.ndarray         # [P, n_in] row in the owner's table
    mask: jnp.ndarray               # [P, b_max] 1.0 for real request slots
    route: jnp.ndarray              # [B] request pos -> shard*b_max + slot

    @property
    def num_hops(self) -> int:
        return len(self.tensors)

    @property
    def num_shards(self) -> int:
        return int(self.shard_seeds.shape[0])

    @property
    def b_max(self) -> int:
        return int(self.shard_seeds.shape[1])

    def slice_labels(self, labels: np.ndarray) -> jnp.ndarray:
        """Per-shard label slabs ``[P, b_max]`` (pad slots carry the pad
        seed's label; masked out of every loss term)."""
        return jnp.asarray(
            np.asarray(labels)[self.shard_seeds].astype(np.int32))


def route_seeds(part: GraphPartition, seeds: np.ndarray):
    """Split a request batch by owner shard, preserving request order.

    Returns ``(shard_seeds [P, b_max], mask [P, b_max], route [B])`` where
    ``b_max`` is the power-of-two bucket of the largest per-shard count and
    pad slots hold the shard's first owned node."""
    seeds = np.asarray(seeds, dtype=np.int32)
    if seeds.ndim != 1 or seeds.size == 0:
        raise ValueError("seeds must be a non-empty 1-D int array")
    num_parts = part.num_parts
    owners = part.owner_of(seeds).astype(np.int64)
    counts = np.bincount(owners, minlength=num_parts)
    b_max = pow2ceil(int(counts.max()))
    # rank of each request within its owner, in request order
    order = np.argsort(owners, kind="stable")
    starts = np.zeros(num_parts + 1, dtype=np.int64)
    starts[1:] = np.cumsum(counts)
    slots = np.empty(len(seeds), dtype=np.int64)
    slots[order] = np.arange(len(seeds)) - starts[owners[order]]
    shard_seeds = np.repeat(
        part.bounds[:num_parts].astype(np.int32)[:, None], b_max, axis=1)
    shard_seeds[owners, slots] = seeds
    mask = (np.arange(b_max)[None, :] < counts[:, None]).astype(np.float32)
    route = (owners * b_max + slots).astype(np.int32)
    return shard_seeds, mask, route


class ShardedBatcher:
    """Samples + assembles ``ShardedMiniBatch``es for a partitioned graph.

    Caching: batches are memoized by seed bytes + epoch + *partition
    identity* (two partitionings of the same graph must never share
    entries), layouts by padded-block content signature."""

    def __init__(self, part: GraphPartition, fanouts: Sequence[FanoutSpec],
                 *, seed: int = 0, tile: int = 128, node_block: int = 128,
                 cache_batches: int = 64, cache_layouts: int = 256):
        self.part = part
        self.sampler = ShardedSampler(part, fanouts, seed=seed)
        self.tile = tile
        self.node_block = node_block
        self._fanout_key = tuple(
            tuple(int(x) for x in f) for f in self.sampler.fanouts)
        self._part_key = (part.num_parts, part.bounds.tobytes())
        self._batch_cache = LRUCache(cache_batches, "dist-batches")
        self._layout_cache = LRUCache(cache_layouts, "dist-layouts")
        self.host_builds = 0

    # ------------------------------------------------------------------
    def _layouts_for(self, g: HeteroGraph) -> codegen.KernelLayouts:
        key = ("fixed", block_signature(g, self.tile, self.node_block, True))
        kl = self._layout_cache.get(key)
        if kl is None:
            kl = build_fixed_layouts(g, tile=self.tile,
                                     node_block=self.node_block)
            self._layout_cache.put(key, kl)
        return kl

    def build(self, seeds: np.ndarray, step: int = 0,
              epoch: Optional[int] = None) -> ShardedMiniBatch:
        seeds = np.asarray(seeds, dtype=np.int32)
        key = (seeds.tobytes(), epoch, self._fanout_key, self.tile,
               self.node_block, self._part_key)
        hit = self._batch_cache.get(key)
        if hit is not None:
            return dataclasses.replace(hit, step=step)
        mb = self._build(seeds, step, epoch)
        self._batch_cache.put(key, mb)
        return mb

    def _build(self, seeds: np.ndarray, step: int,
               epoch: Optional[int]) -> ShardedMiniBatch:
        self.host_builds += 1
        num_parts = self.part.num_parts
        shard_seeds, mask, route = route_seeds(self.part, seeds)
        seqs = [self.sampler.sample_for_shard(
                    p, shard_seeds[p], batch_index=step, epoch=epoch)
                for p in range(num_parts)]
        num_hops = len(seqs[0].blocks)

        # pad every shard's hop-h block to the common cross-shard buckets
        padded = []
        for h in range(num_hops):
            n_t, e_t, u_t = common_block_targets(
                [s.blocks[h].graph for s in seqs])
            row = [pad_block_graph(s.blocks[h].graph, n_t, e_t, u_t)
                   for s in seqs]
            assert all(g.num_nodes == n_t for g in row)
            padded.append(row)

        # hop-chaining gathers, padded to the (common) downstream buckets
        n_in = padded[0][0].num_nodes
        input_ids = np.stack([pad_index(s.input_node_ids, n_in)
                              for s in seqs])
        last_rows = max(pow2ceil(s.blocks[-1].dst_local.shape[0])
                        for s in seqs)
        dst_locals = []
        for h in range(num_hops):
            tgt = (padded[h + 1][0].num_nodes if h + 1 < num_hops
                   else last_rows)
            dst_locals.append(jnp.asarray(np.stack(
                [pad_index(s.blocks[h].dst_local, tgt) for s in seqs])))

        return ShardedMiniBatch(
            step=step,
            seeds=seeds,
            shard_seeds=shard_seeds,
            tensors=[stack_pytrees([g.to_tensors() for g in row])
                     for row in padded],
            layouts=[stack_pytrees([self._layouts_for(g) for g in row])
                     for row in padded],
            dst_locals=dst_locals,
            seed_perm=jnp.asarray(np.stack([s.seed_perm for s in seqs])),
            owner_rows=jnp.asarray(self.part.owner_of(input_ids)),
            local_rows=jnp.asarray(self.part.local_row(input_ids)),
            mask=jnp.asarray(mask),
            route=jnp.asarray(route),
        )

    def stats(self) -> dict:
        return {
            "host_builds": self.host_builds,
            "batch_cache": self._batch_cache.stats(),
            "layout_cache": self._layout_cache.stats(),
            **self.sampler.stats(),
        }
