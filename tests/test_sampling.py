"""Hetero mini-batch sampling subsystem: determinism, block layout
invariants, full-fanout equivalence with the full-graph forward, bucketing,
the prefetching loader, the layout/block caches, the whole-plan compiled
executor, and the serving driver."""
import collections

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.executor import signature as executor_signature
from repro.core.graph import HeteroGraph, synthetic_heterograph
from repro.core.module import HectorStack
from repro.models import hgt_program, rgat_program, rgcn_program
from repro.sampling import (FanoutSampler, LRUCache, MiniBatchLoader,
                            SeedStream, block_signature, build_minibatch)
from repro.sampling.bucketing import pad_block_graph


@pytest.fixture(scope="module")
def graph():
    return synthetic_heterograph(num_nodes=120, num_edges=900, num_ntypes=4,
                                 num_etypes=7, seed=0)


@pytest.fixture(scope="module")
def feats(graph):
    rng = np.random.default_rng(1)
    return jnp.asarray(rng.normal(size=(graph.num_nodes, 16)), jnp.float32)


SEEDS = np.array([3, 50, 7, 3, 119, 0], dtype=np.int32)  # dupes on purpose


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
def test_sampler_deterministic_under_seed(graph):
    a = FanoutSampler(graph, [3, 5], seed=11).sample(SEEDS, batch_index=4)
    b = FanoutSampler(graph, [3, 5], seed=11).sample(SEEDS, batch_index=4)
    for ba, bb in zip(a.blocks, b.blocks):
        np.testing.assert_array_equal(ba.graph.src, bb.graph.src)
        np.testing.assert_array_equal(ba.graph.dst, bb.graph.dst)
        np.testing.assert_array_equal(ba.graph.etype, bb.graph.etype)
        np.testing.assert_array_equal(ba.node_ids, bb.node_ids)
    np.testing.assert_array_equal(a.seed_perm, b.seed_perm)


def test_sampler_varies_with_batch_index(graph):
    s = FanoutSampler(graph, [2, 2], seed=0)
    a = s.sample(SEEDS, batch_index=0)
    b = s.sample(SEEDS, batch_index=1)
    same = (a.blocks[0].graph.num_edges == b.blocks[0].graph.num_edges
            and np.array_equal(a.blocks[0].node_ids, b.blocks[0].node_ids))
    assert not same


# ---------------------------------------------------------------------------
# block invariants
# ---------------------------------------------------------------------------
def _check_block_graph(bg: HeteroGraph):
    # etype-sorted canonical edges + consistent segment pointers
    assert np.all(np.diff(bg.etype) >= 0)
    np.testing.assert_array_equal(
        bg.etype_ptr,
        np.concatenate([[0], np.cumsum(np.bincount(
            bg.etype, minlength=bg.num_etypes))]))
    # dst CSR is a valid partition of the dst-sorted edges
    assert bg.dst_ptr[0] == 0 and bg.dst_ptr[-1] == bg.num_edges
    assert np.all(np.diff(bg.dst_ptr) >= 0)
    np.testing.assert_array_equal(bg.dst[bg.perm_dst], bg.dst_sorted)
    assert np.all(np.diff(bg.dst_sorted) >= 0)
    # compact materialization map resolves to the original (src, etype)
    np.testing.assert_array_equal(bg.unique_src[bg.edge_to_unique], bg.src)
    np.testing.assert_array_equal(bg.unique_etype[bg.edge_to_unique], bg.etype)
    assert np.all(np.diff(bg.unique_etype) >= 0)
    assert bg.num_unique <= max(1, bg.num_edges)
    # nodes presorted by type
    assert np.all(np.diff(bg.node_type) >= 0)


def test_block_layout_invariants(graph):
    seq = FanoutSampler(graph, [4, 2, 3], seed=3).sample(SEEDS)
    assert seq.num_hops == 3
    for i, blk in enumerate(seq.blocks):
        _check_block_graph(blk.graph)
        # local/global ID mapping is consistent
        assert blk.node_ids.shape[0] == blk.graph.num_nodes
        assert np.all(np.diff(blk.node_ids) > 0)
        np.testing.assert_array_equal(
            graph.node_type[blk.node_ids], blk.graph.node_type)
        # every sampled edge exists in the parent graph
        full = set(zip(graph.src.tolist(), graph.dst.tolist(),
                       graph.etype.tolist()))
        for s, d, t in zip(blk.node_ids[blk.graph.src],
                           blk.node_ids[blk.graph.dst], blk.graph.etype):
            assert (s, d, t) in full
        # chaining: this hop's dst frontier is the next hop's node set
        if i + 1 < seq.num_hops:
            np.testing.assert_array_equal(
                blk.dst_ids, seq.blocks[i + 1].node_ids)
    np.testing.assert_array_equal(
        seq.blocks[-1].dst_ids[seq.seed_perm], SEEDS)


def test_fanout_cap_respected(graph):
    fanouts = [2, 4]
    seq = FanoutSampler(graph, fanouts, seed=9).sample(
        np.arange(30, dtype=np.int32), batch_index=1)
    for blk, cap in zip(seq.blocks, fanouts):
        per_pair = collections.Counter(
            zip(blk.graph.dst.tolist(), blk.graph.etype.tolist()))
        assert max(per_pair.values(), default=0) <= cap


def test_full_fanout_keeps_entire_neighborhood(graph):
    seq = FanoutSampler(graph, [-1], seed=0).sample(SEEDS)
    blk = seq.blocks[0]
    sampled_in_deg = np.bincount(blk.node_ids[blk.graph.dst],
                                 minlength=graph.num_nodes)
    full_in_deg = np.diff(graph.dst_ptr)
    for v in np.unique(SEEDS):
        assert sampled_in_deg[v] == full_in_deg[v]


def test_bucketed_block_graph_is_padded_superset(graph):
    seq = FanoutSampler(graph, [3, 3], seed=5).sample(SEEDS)
    for blk in seq.blocks:
        bg = blk.graph
        padded = pad_block_graph(bg)
        _check_block_graph(padded)
        for dim in (padded.num_nodes, padded.num_edges, padded.num_unique):
            assert dim & (dim - 1) == 0  # power of two
        # real edges survive: pad edges all point at pad nodes
        real = (padded.src < bg.num_nodes) & (padded.dst < bg.num_nodes)
        assert int(real.sum()) == bg.num_edges
        key = lambda s, d, t: set(zip(s.tolist(), d.tolist(), t.tolist()))
        assert key(padded.src[real], padded.dst[real], padded.etype[real]) \
            == key(bg.src, bg.dst, bg.etype)


# ---------------------------------------------------------------------------
# sampled forward == full-graph forward at full fanout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prog_fn", [rgcn_program, rgat_program, hgt_program])
@pytest.mark.parametrize("bucket", [False, True])
def test_full_fanout_matches_full_graph(graph, feats, prog_fn, bucket):
    stack = HectorStack([prog_fn(16, 12), prog_fn(12, 6)], graph,
                        tile=8, node_block=8, jit=False)
    params = stack.init(jax.random.key(0))
    full = stack.apply(params, {"feature": feats})
    seq = FanoutSampler(graph, [-1, -1], seed=0).sample(SEEDS)
    mb = build_minibatch(seq, tile=8, node_block=8, bucket=bucket)
    out = stack.apply_blocks(params, mb, feats)
    assert out.shape == (len(SEEDS), 6)
    np.testing.assert_allclose(out, full[SEEDS], rtol=2e-4, atol=2e-4)


def test_full_fanout_matches_full_graph_pallas(graph, feats):
    stack = HectorStack([rgat_program(16, 12), rgat_program(12, 6)], graph,
                        tile=8, node_block=8, backend="pallas_interpret",
                        jit=False)
    params = stack.init(jax.random.key(0))
    full = stack.apply(params, {"feature": feats})
    mb = build_minibatch(FanoutSampler(graph, [-1, -1]).sample(SEEDS),
                         tile=8, node_block=8, bucket=True)
    out = stack.apply_blocks(params, mb, feats)
    np.testing.assert_allclose(out, full[SEEDS], rtol=2e-4, atol=2e-4)


def test_partial_fanout_runs_and_is_finite(graph, feats):
    stack = HectorStack([rgat_program(16, 12), rgat_program(12, 6)], graph,
                        tile=8, node_block=8, jit=False)
    params = stack.init(jax.random.key(0))
    mb = build_minibatch(FanoutSampler(graph, [2, 3], seed=1).sample(SEEDS),
                         tile=8, node_block=8, bucket=True)
    out = stack.apply_blocks(params, mb, feats)
    assert out.shape == (len(SEEDS), 6)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# loader
# ---------------------------------------------------------------------------
def test_loader_prefetch_deterministic_and_bounded(graph):
    sampler = FanoutSampler(graph, [3, 3], seed=2)
    stream = SeedStream(graph.num_nodes, 8, seed=5)
    a = MiniBatchLoader(sampler, stream, tile=8, node_block=8, num_batches=3)
    b = MiniBatchLoader(sampler, stream, tile=8, node_block=8, num_batches=3)
    try:
        batches_a, batches_b = list(a), list(b)
    finally:
        a.close()
        b.close()
    assert [mb.step for mb in batches_a] == [0, 1, 2]
    for ma, mb_ in zip(batches_a, batches_b):
        np.testing.assert_array_equal(ma.seq.blocks[0].graph.src,
                                      mb_.seq.blocks[0].graph.src)
        np.testing.assert_array_equal(np.asarray(ma.input_ids),
                                      np.asarray(mb_.input_ids))
    # exhausted loader keeps raising StopIteration
    with pytest.raises(StopIteration):
        next(a)


def test_loader_close_mid_stream(graph):
    sampler = FanoutSampler(graph, [2], seed=0)
    loader = MiniBatchLoader(sampler, SeedStream(graph.num_nodes, 4, seed=0),
                             tile=8, node_block=8)
    next(loader)
    loader.close()
    assert not loader._thread.is_alive()


# ---------------------------------------------------------------------------
# compile cache / layout cache / sampled-block cache
# ---------------------------------------------------------------------------
def test_block_executor_compile_cache_hits_same_bucket(graph, feats):
    """Same-bucket blocks -> one trace + cache hit; a new bucket -> miss."""
    stack = HectorStack([rgat_program(16, 12), rgat_program(12, 6)], graph,
                        tile=8, node_block=8, jit=False)
    params = stack.init(jax.random.key(0))
    ex = stack.block_executor
    sampler = FanoutSampler(graph, [2, 2], seed=0)
    mb0 = build_minibatch(sampler.sample(SEEDS, batch_index=0),
                          tile=8, node_block=8, bucket=True)
    mb1 = build_minibatch(sampler.sample(SEEDS, batch_index=1),
                          tile=8, node_block=8, bucket=True)
    out0 = stack.apply_blocks(params, mb0, feats, compiled=True)
    assert (ex.trace_count, ex.cache_misses, ex.cache_hits) == (1, 1, 0)
    stack.apply_blocks(params, mb0, feats, compiled=True)
    assert (ex.trace_count, ex.cache_hits) == (1, 1)
    # eager path agrees with the compiled one
    np.testing.assert_allclose(
        out0, stack.apply_blocks(params, mb0, feats, compiled=False),
        rtol=2e-4, atol=2e-4)
    # a different sample in the same buckets: still zero retraces
    if executor_signature((mb1.tensors, mb1.layouts)) == \
            executor_signature((mb0.tensors, mb0.layouts)):
        stack.apply_blocks(params, mb1, feats, compiled=True)
        assert ex.trace_count == 1
    # a structurally different batch (more seeds -> larger buckets): miss
    big = build_minibatch(
        sampler.sample(np.arange(60, dtype=np.int32), batch_index=2),
        tile=8, node_block=8, bucket=True)
    stack.apply_blocks(params, big, feats, compiled=True)
    assert ex.cache_misses == 2 and ex.trace_count == 2


def test_lru_cache_eviction_and_counters():
    c = LRUCache(maxsize=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1          # refresh 'a': now 'b' is LRU
    c.put("c", 3)                   # evicts 'b'
    assert c.evictions == 1
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    assert c.hits == 3 and c.misses == 1
    assert 0 < c.hit_rate < 1


def test_kernel_layouts_cache_by_block_signature(graph):
    sampler = FanoutSampler(graph, [3, 3], seed=7)
    seq = sampler.sample(SEEDS, batch_index=0)
    cache = LRUCache(maxsize=16)
    mb_a = build_minibatch(seq, tile=8, node_block=8, bucket=True,
                           layout_cache=cache)
    assert cache.misses == mb_a.num_hops and cache.hits == 0
    # identical sample again: all hops hit, layouts are the same objects
    mb_b = build_minibatch(seq, tile=8, node_block=8, bucket=True,
                           layout_cache=cache)
    assert cache.hits == mb_a.num_hops
    for la, lb in zip(mb_a.layouts, mb_b.layouts):
        assert la is lb
    # the signature really is content-based: a different sample differs
    other = sampler.sample(SEEDS, batch_index=1)
    keys = {block_signature(b.graph, 8, 8, True) for b in seq.blocks}
    keys_other = {block_signature(b.graph, 8, 8, True) for b in other.blocks}
    assert keys != keys_other


def test_loader_block_cache_zero_rebuilds_on_repeats(graph, feats):
    """Repeated seed batches: served from the block cache (no sampling, no
    host-side KernelLayouts rebuilds) and with zero executor retraces."""
    distinct, total = 2, 8
    stack = HectorStack([rgat_program(16, 12), rgat_program(12, 6)], graph,
                        tile=8, node_block=8, jit=False)
    params = stack.init(jax.random.key(1))
    ex = stack.block_executor
    loader = MiniBatchLoader(
        FanoutSampler(graph, [3, 3], seed=2),
        SeedStream(graph.num_nodes, 6, seed=5, num_distinct=distinct),
        tile=8, node_block=8, bucket=True, num_batches=total,
        cache_blocks=8, cache_layouts=32,
    )
    outs = []
    try:
        for mb in loader:
            outs.append(np.asarray(
                stack.apply_blocks(params, mb, feats, compiled=True)))
    finally:
        loader.close()
    assert len(outs) == total
    stats = loader.cache_stats()
    assert stats["block_cache"]["misses"] == distinct
    assert stats["block_cache"]["hits"] == total - distinct
    # layout builds happened only for the distinct batches
    assert stats["layout_cache"]["misses"] <= distinct * 2  # hops per batch
    # compiled executor: traced at most once per distinct bucket, and every
    # repeat was a compile-cache hit
    assert ex.trace_count <= distinct
    assert ex.cache_hits >= total - distinct
    # repeats reproduce the first occurrence bit-for-bit
    for i in range(distinct, total):
        np.testing.assert_array_equal(outs[i], outs[i % distinct])


def test_loader_block_cache_epoch_keyed_for_training_streams(graph):
    """Regression (ISSUE 3 satellite): the sampled-block LRU used to be
    keyed by (seeds, fanout) only, so a training stream revisiting the same
    seed batch in a later epoch would silently replay the *identical*
    cached blocks — destroying neighbor-sampling stochasticity. With an
    epoch-aware seed source the key (and the sampler rng) includes the
    epoch: same seeds, later epoch -> fresh sample, zero cache hits."""
    train_seeds = np.arange(32, dtype=np.int32)

    def edge_key(mb):
        b = mb.seq.blocks[0]
        return (b.node_ids[b.graph.src].tobytes(),
                b.node_ids[b.graph.dst].tobytes())

    class ConstantEpochStream:
        """Same seed batch every step; one step per 'epoch'."""
        def batch(self, step):
            return train_seeds

        def epoch_of(self, step):
            return step

    loader = MiniBatchLoader(FanoutSampler(graph, [3, 3], seed=0),
                             ConstantEpochStream(), tile=8, node_block=8,
                             bucket=True, num_batches=3, cache_blocks=8)
    try:
        keys = [edge_key(mb) for mb in loader]
        cache = loader.block_cache.stats()
    finally:
        loader.close()
    assert len(set(keys)) == len(keys), (
        "same seed batch replayed identical blocks across epochs "
        "(block cache is not epoch-keyed)")
    assert cache["hits"] == 0, (
        f"{cache['hits']} block-cache hits across epochs for a training "
        f"stream (expected 0: fresh sample each epoch)")

    # serving streams (no epoch_of) keep the replay semantics: same seeds
    # at a later step return the cached block
    seeds = np.arange(24, dtype=np.int32)
    sampler = FanoutSampler(graph, [3, 3], seed=2)
    loader = MiniBatchLoader(sampler, lambda step: seeds, tile=8,
                             node_block=8, bucket=True, num_batches=3,
                             cache_blocks=8)
    try:
        batches = list(loader)
        stats = loader.cache_stats()["block_cache"]
    finally:
        loader.close()

    def edges(mb):
        b = mb.seq.blocks[0]
        return set(zip(b.node_ids[b.graph.src].tolist(),
                       b.node_ids[b.graph.dst].tolist(),
                       b.graph.etype.tolist()))

    assert stats["hits"] == 2 and stats["misses"] == 1
    assert edges(batches[0]) == edges(batches[1]) == edges(batches[2])


# ---------------------------------------------------------------------------
# serving driver
# ---------------------------------------------------------------------------
def test_serve_rgnn_end_to_end():
    from repro.launch import serve_rgnn
    stats = serve_rgnn.serve(
        model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
        classes=4, fanouts=[3, 3], batch_size=8, num_batches=3,
        tile=8, node_block=8, log=lambda *a, **k: None,
    )
    assert stats["batches"] == 3
    assert stats["latency_ms_p50"] > 0
    assert stats["seeds_per_s"] > 0
    assert stats["last_preds"].shape == (8,)


# ---------------------------------------------------------------------------
# fanout normalization
# ---------------------------------------------------------------------------
def test_dict_fanout_warns_on_unlisted_etypes(graph):
    """Pin the (surprising) dict-fanout default: etypes absent from the dict
    sample zero edges, and the sampler now says so out loud."""
    from repro.sampling.sampler import normalize_fanout

    with pytest.warns(UserWarning, match="unlisted"):
        f = normalize_fanout({0: 3, 2: 5}, graph.num_etypes)
    np.testing.assert_array_equal(
        f, [3, 0, 5] + [0] * (graph.num_etypes - 3))
    # sampling with it really draws no edges of the unlisted etypes
    with pytest.warns(UserWarning):
        seq = FanoutSampler(graph, [{0: 3, 2: 5}], seed=0).sample(SEEDS)
    assert set(np.unique(seq.blocks[0].graph.etype)) <= {0, 2}
    # a complete dict stays silent
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        normalize_fanout({e: 2 for e in range(graph.num_etypes)},
                         graph.num_etypes)


# ---------------------------------------------------------------------------
# device-native sampling (ISSUE 7): host/device parity + retrace-freeness
# ---------------------------------------------------------------------------
from repro.sampling import DeviceSampler  # noqa: E402


def _device_block_edges(mb, hop, num_nodes):
    """Global (src, dst, etype) multiset of one device block's real edges."""
    nid = np.asarray(mb.seq.blocks[hop].node_ids)
    gt = mb.tensors[hop]
    src_g = nid[np.asarray(gt.src)]
    dst_g = nid[np.asarray(gt.dst)]
    et = np.asarray(gt.etype)
    valid = (src_g < num_nodes) & (dst_g < num_nodes)
    return sorted(zip(src_g[valid].tolist(), dst_g[valid].tolist(),
                      et[valid].tolist()))


@pytest.mark.parametrize("fanouts", [[3, 3], [2, 4], [1]])
def test_device_sampler_matches_host_blocks(graph, fanouts):
    """The determinism contract: for the same (seed, batch_index, epoch)
    stream position both pipelines select the same edge multisets per
    (dst, etype) and produce the same frontier node sets."""
    host = FanoutSampler(graph, fanouts, seed=11)
    dev = DeviceSampler(graph, fanouts, seed=11, tile=8, node_block=8)
    for bi in (0, 1, 5):
        seq = host.sample(SEEDS, batch_index=bi)
        mb = dev.sample_minibatch(SEEDS, batch_index=bi)
        for hop in range(len(fanouts)):
            hb = seq.blocks[hop]
            host_edges = sorted(zip(
                hb.node_ids[hb.graph.src].tolist(),
                hb.node_ids[hb.graph.dst].tolist(),
                hb.graph.etype.tolist()))
            assert _device_block_edges(mb, hop, graph.num_nodes) \
                == host_edges
            nid = np.asarray(mb.seq.blocks[hop].node_ids)
            np.testing.assert_array_equal(nid[nid < graph.num_nodes],
                                          hb.node_ids)
        np.testing.assert_array_equal(np.asarray(mb.seed_perm),
                                      seq.seed_perm)


def test_device_sampler_epoch_rekeys_stream(graph):
    dev = DeviceSampler(graph, [3, 3], seed=0, tile=8, node_block=8)
    a = _device_block_edges(dev.sample_minibatch(SEEDS, epoch=0), 0,
                            graph.num_nodes)
    b = _device_block_edges(dev.sample_minibatch(SEEDS, epoch=1), 0,
                            graph.num_nodes)
    host = FanoutSampler(graph, [3, 3], seed=0)
    ha = host.sample(SEEDS, epoch=0).blocks[0]
    assert a != b
    assert a == sorted(zip(ha.node_ids[ha.graph.src].tolist(),
                           ha.node_ids[ha.graph.dst].tolist(),
                           ha.graph.etype.tolist()))


@pytest.mark.parametrize("prog_fn", [rgcn_program, rgat_program,
                                     hgt_program])
def test_device_minibatch_forward_matches_host(graph, feats, prog_fn):
    """A device-built MiniBatch is a drop-in: same per-seed outputs as the
    host-built one for the same stream position."""
    stack = HectorStack([prog_fn(16, 12), prog_fn(12, 6)], graph,
                        tile=8, node_block=8, jit=False)
    params = stack.init(jax.random.key(0))
    mb_h = build_minibatch(
        FanoutSampler(graph, [3, 3], seed=11).sample(SEEDS, batch_index=2),
        tile=8, node_block=8, bucket=True)
    mb_d = DeviceSampler(graph, [3, 3], seed=11, tile=8, node_block=8) \
        .sample_minibatch(SEEDS, batch_index=2)
    out_h = stack.apply_blocks(params, mb_h, feats)
    out_d = stack.apply_blocks(params, mb_d, feats)
    np.testing.assert_allclose(out_d, out_h, rtol=2e-4, atol=2e-4)


def test_device_full_fanout_matches_full_graph(graph, feats):
    stack = HectorStack([rgat_program(16, 12), rgat_program(12, 6)], graph,
                        tile=8, node_block=8, jit=False)
    params = stack.init(jax.random.key(0))
    full = stack.apply(params, {"feature": feats})
    mb = DeviceSampler(graph, [-1, -1], seed=0, tile=8, node_block=8) \
        .sample_minibatch(SEEDS)
    out = stack.apply_blocks(params, mb, feats)
    assert out.shape == (len(SEEDS), 6)
    np.testing.assert_allclose(out, full[SEEDS], rtol=2e-4, atol=2e-4)


def test_device_sampler_retrace_free_in_steady_state(graph):
    """Fixed-shape bucketing: recurring stream positions (the power-law
    serving assumption — same seeds at the same batch_index resample the
    same buckets) replay already-traced programs, zero jit retraces — and
    the sampling loop itself never blocks on a count readback."""
    dev = DeviceSampler(graph, [3, 3], seed=2, tile=8, node_block=8)
    stream = SeedStream(graph.num_nodes, 6, seed=5, num_distinct=3)
    # warmup cycle 1 traces the worst-case buckets; the drain barrier lands
    # every count inspection; cycle 2 traces the shrunken buckets
    for step in range(3):
        dev.sample_minibatch(stream.batch(step), batch_index=step % 3)
    dev.drain(block=True)
    assert dev.bucket_shrinks > 0
    for step in range(3, 6):
        dev.sample_minibatch(stream.batch(step), batch_index=step % 3)
    dev.drain(block=True)
    warm = dev.trace_count
    syncs = dev.count_syncs
    assert warm == dev.cache_misses
    for step in range(6, 12):
        dev.sample_minibatch(stream.batch(step), batch_index=step % 3)
    assert dev.trace_count == warm
    assert dev.cache_hits > 0
    assert dev.count_syncs == syncs   # steady state issued zero readbacks
    dev.drain(block=True)
    assert dev.bucket_overflows == 0  # no shrunken bucket truncated a batch


def test_device_loader_threadless_prefetch(graph, feats):
    """MiniBatchLoader in device mode: same iteration/StopIteration contract
    and block-cache semantics, zero host pipeline builds."""
    dev = DeviceSampler(graph, [3, 3], seed=2, tile=8, node_block=8)
    distinct, total = 2, 6
    loader = MiniBatchLoader(
        dev, SeedStream(graph.num_nodes, 6, seed=5, num_distinct=distinct),
        tile=8, node_block=8, bucket=True, num_batches=total,
        cache_blocks=8)
    try:
        batches = list(loader)
    finally:
        loader.close()
    assert loader.mode == "device"
    assert [mb.step for mb in batches] == list(range(total))
    assert loader.host_builds == 0
    assert loader.device_builds == distinct   # repeats hit the block cache
    assert loader.cache_stats()["block_cache"]["hits"] == total - distinct
    with pytest.raises(StopIteration):
        next(loader)
    # repeated batches reference the same device-built blocks
    np.testing.assert_array_equal(
        np.asarray(batches[0].tensors[0].src),
        np.asarray(batches[distinct].tensors[0].src))


def test_device_graph_csc_consistent(graph):
    """The uploaded CSC is exactly the (dst-major, etype-minor) view of the
    host graph's dst-sorted edges."""
    dg = graph.to_device_graph()
    indptr = np.asarray(dg.csc_indptr)
    csc_src = np.asarray(dg.csc_src)
    assert indptr[-1] == graph.num_edges
    r = graph.num_etypes
    et_dst_sorted = graph.etype[graph.perm_dst]
    for v, t in [(3, 0), (50, 2), (119, r - 1)]:
        lo, hi = indptr[v * r + t], indptr[v * r + t + 1]
        mask = (graph.dst_sorted == v) & (et_dst_sorted == t)
        np.testing.assert_array_equal(csc_src[lo:hi],
                                      graph.src[graph.perm_dst][mask])


# ---------------------------------------------------------------------------
# zipf-skewed seed stream + loader cache-rate reporting (ISSUE 9 satellites)
# ---------------------------------------------------------------------------
def test_seed_stream_zipf_deterministic_and_pinned():
    a = SeedStream(200, 64, seed=9, zipf_alpha=1.2)
    b = SeedStream(200, 64, seed=9, zipf_alpha=1.2)
    np.testing.assert_array_equal(a.batch(3), b.batch(3))
    # pure function of (seed, step): replaying a step yields the same batch
    np.testing.assert_array_equal(a.batch(3), a.batch(3))
    assert a.batch(0).dtype == np.int32
    # the distribution is *pinned*: inverse-CDF draws over rank
    # probabilities (r+1)^-alpha mapped through the seed-keyed rank
    # permutation, reproduced here from the documented spec
    rng = np.random.default_rng((9, 3))
    p = np.arange(1, 201, dtype=np.float64) ** -1.2
    cdf = np.cumsum(p / p.sum())
    ranks = np.searchsorted(cdf, rng.random(64), side="right")
    r2i = np.random.default_rng((9, 0x5eed)).permutation(200).astype(np.int64)
    np.testing.assert_array_equal(
        a.batch(3), r2i[np.minimum(ranks, 199)].astype(np.int32))


def test_seed_stream_zipf_skews_traffic():
    n = 500
    s = SeedStream(n, 256, seed=1, zipf_alpha=1.2)
    draws = np.concatenate([s.batch(t) for t in range(40)])
    counts = np.bincount(draws, minlength=n)
    top = np.sort(counts)[::-1]
    # power law: the top 10% of nodes absorb the majority of traffic,
    # which a uniform stream cannot produce at this sample size
    assert top[: n // 10].sum() / counts.sum() > 0.5
    # the hottest node is the permuted rank-0 id, not simply id 0
    assert np.argmax(counts) == s._rank2idx[0]


def test_seed_stream_uniform_path_bitwise_unchanged():
    # adding the skew knob must not perturb existing uniform streams: the
    # draw is pinned to the exact pre-knob Generator call (incl. dtype)
    s = SeedStream(120, 16, seed=4)
    expected = np.random.default_rng((4, 7)).integers(
        0, 120, size=16, dtype=np.int32)
    np.testing.assert_array_equal(s.batch(7), expected)


def test_seed_stream_ids_population():
    ids = np.array([5, 17, 40, 99], dtype=np.int32)
    s = SeedStream(ids=ids, batch_size=32, seed=0, zipf_alpha=1.5)
    assert s.num_nodes == 4
    assert set(s.batch(0).tolist()) <= set(ids.tolist())
    u = SeedStream(ids=ids, batch_size=32, seed=0)
    assert set(u.batch(0).tolist()) <= set(ids.tolist())
    with pytest.raises(ValueError):
        SeedStream(ids=np.empty(0, np.int32))
    with pytest.raises(ValueError):
        SeedStream(100, zipf_alpha=0.0)


def test_loader_stats_report_cache_hit_rates(graph):
    """build_stats()/cache_stats() carry per-cache hit *rates* and the
    LRU mirrors them into the metrics registry."""
    from repro import obs
    distinct, total = 2, 8
    with obs.scope(metrics=True) as sc:
        loader = MiniBatchLoader(
            FanoutSampler(graph, [3, 3], seed=2),
            SeedStream(graph.num_nodes, 6, seed=5, num_distinct=distinct),
            tile=8, node_block=8, bucket=True, num_batches=total,
            cache_blocks=8, cache_layouts=32,
        )
        try:
            for _ in loader:
                pass
        finally:
            loader.close()
        bs = loader.build_stats()
        want = (total - distinct) / total
        assert bs["block_cache_hit_rate"] == pytest.approx(want)
        assert 0.0 <= bs["layout_cache_hit_rate"] <= 1.0
        cs = loader.cache_stats()
        assert cs["block_cache"]["hit_rate"] == pytest.approx(want)
        snap = sc.registry.snapshot()
        rates = [m for m in snap["gauges"]
                 if m["name"] == "loader_cache_hit_rate"
                 and m["labels"].get("cache") == "block_cache"]
        assert rates and rates[0]["value"] == pytest.approx(want)


# ---------------------------------------------------------------------------
# loader failure / end-of-stream contracts (the serving runtime's hooks)
# ---------------------------------------------------------------------------
def test_loader_worker_exception_propagates(graph):
    """Regression: a crash anywhere in the producer pipeline must re-raise
    in the consumer (it used to sit in the prefetch queue behind built
    batches with the consumer eventually stalling), with the worker thread
    stopped and joined first."""
    sampler = FanoutSampler(graph, [2], seed=0)

    def bad_source(step):
        if step == 2:
            raise ValueError("seed source exploded at step 2")
        return np.arange(4, dtype=np.int32)

    loader = MiniBatchLoader(sampler, bad_source, tile=8, node_block=8)
    try:
        assert next(loader).step == 0
        assert next(loader).step == 1
        with pytest.raises(ValueError, match="exploded at step 2"):
            next(loader)
        # terminal after the failure: no hang, no resurrected worker
        with pytest.raises(StopIteration):
            next(loader)
    finally:
        loader.close()
    assert not loader._thread.is_alive()


def test_loader_callable_source_none_ends_stream(graph):
    """A callable seed source may return None to end an unbounded stream
    (how the serving runtime drains its loader at shutdown)."""
    sampler = FanoutSampler(graph, [2], seed=0)

    def source(step):
        return np.arange(4, dtype=np.int32) if step < 3 else None

    loader = MiniBatchLoader(sampler, source, tile=8, node_block=8)
    try:
        assert [mb.step for mb in loader] == [0, 1, 2]
        with pytest.raises(StopIteration):
            next(loader)
    finally:
        loader.close()
    assert not loader._thread.is_alive()


def test_shape_floors_converge_to_one_shape_set(graph):
    """Grow-only floors: batches at one seed count converge to a single
    signature (floors absorb per-hop bucket jitter, including the
    layout-internal segment-row buckets)."""
    from repro.sampling.bucketing import ShapeFloors
    from repro.sampling.loader import build_minibatch

    sampler = FanoutSampler(graph, [3, 3], seed=7)
    floors = ShapeFloors()
    sigs = []
    for i in range(12):
        seeds = np.random.default_rng(i).integers(
            0, graph.num_nodes, 8).astype(np.int32)
        seq = sampler.sample(seeds, batch_index=i)
        mb = build_minibatch(seq, step=i, tile=8, node_block=8, bucket=True,
                             shape_floors=floors)
        sigs.append(executor_signature(
            (mb.tensors, mb.layouts, mb.input_ids, mb.dst_locals,
             mb.seed_perm)))
        if i == 5:
            floors.bump(1)   # calibration-style headroom
    # after the floors saturate (probe + bump), signatures are constant
    tail = sigs[6:]
    assert all(s == tail[0] for s in tail)
    assert floors.growths >= 0
