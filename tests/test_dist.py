"""Data-parallel execution over a partitioned hetero graph (``repro.dist``).

Single-device tests pin the layer's parity contracts — the partitioner's
covering invariants, the sharded sampler drawing the *same* counter-based
key stream as the single-box ``FanoutSampler``, seed routing, batcher
caching, and the ``shard_map`` serve/train steps matching the plain block
executors. The subprocess tests force 4 CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``) and pin device-count
invariance: dp=4 must be *bitwise* identical to dp=1 because every compiled
collective reduces over the stacked shard axis of length P, independent of
how the shards fold onto devices.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import executor
from repro.core.graph import synthetic_heterograph
from repro.dist import (ShardedBatcher, ShardedSampler, check_partition,
                        partition_graph)
from repro.dist.data import route_seeds
from repro.optim import AdamW
from repro.sampling import FanoutSampler, MiniBatchLoader, SeedStream, \
    build_minibatch
from repro.sampling.loader import LRUCache
from repro.train import EngineConfig, RGNNEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 600):
    """Run ``code`` in a child process that sees ``devices`` forced host
    CPU devices, so this process keeps seeing one; returns its stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout

SEEDS = np.array([3, 50, 7, 3, 119, 0, 88, 12], dtype=np.int32)


@pytest.fixture(scope="module")
def graph():
    return synthetic_heterograph(120, 900, 4, 7, seed=0)


@pytest.fixture(scope="module")
def part(graph):
    return partition_graph(graph, 4)


@pytest.fixture(scope="module")
def dist_engine(graph):
    """Engine with the distributed surface on (4 shards, 1 device)."""
    return RGNNEngine(graph, EngineConfig(
        model="rgat", layers=2, dim=16, hidden=12, classes=6,
        fanouts=[3, 3], tile=8, node_block=8, seed=0, partitions=4))


@pytest.fixture(scope="module")
def feats(graph):
    rng = np.random.default_rng(1)
    return jnp.asarray(rng.normal(size=(graph.num_nodes, 16)), jnp.float32)


@pytest.fixture(scope="module")
def labels(graph):
    return np.asarray(np.random.default_rng(2).integers(
        0, 6, graph.num_nodes))


# ---------------------------------------------------------------------------
# partitioner
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_parts", [1, 3, 4])
def test_partition_invariants(graph, num_parts):
    """Edge-cut-by-dst covering invariants: shards tile the node range,
    every edge lands in exactly its dst's owner with its global dst-sorted
    position preserved, halos are the out-of-shard sources."""
    assert check_partition(partition_graph(graph, num_parts))


def test_partition_explicit_bounds(graph):
    part = partition_graph(graph, 2, bounds=np.array([0, 30, 120]))
    assert check_partition(part)
    assert part.shards[0].num_owned == 30
    np.testing.assert_array_equal(part.owner_of(np.array([0, 29, 30, 119])),
                                  [0, 0, 1, 1])


def test_partition_errors(graph):
    with pytest.raises(ValueError):
        partition_graph(graph, 0)
    with pytest.raises(ValueError):
        partition_graph(graph, graph.num_nodes + 1)


def test_shard_features_zero_padded(part, feats):
    sf = part.shard_features(np.asarray(feats))
    assert sf.shape[0] == part.num_parts
    for p in range(part.num_parts):
        lo, hi = int(part.bounds[p]), int(part.bounds[p + 1])
        np.testing.assert_array_equal(sf[p, :hi - lo], np.asarray(feats)[lo:hi])
        assert not sf[p, hi - lo:].any()


# ---------------------------------------------------------------------------
# sharded sampler: same key stream as the single-box sampler
# ---------------------------------------------------------------------------
def test_sharded_sampler_bit_identical_to_fanout_sampler(graph, part):
    """Selection is keyed by full-graph dst-sorted edge positions, so a
    shard sampling its owned seeds draws exactly the blocks the single-box
    sampler draws for the same seeds at the same stream position."""
    ss = ShardedSampler(part, [3, 3], seed=0)
    host = FanoutSampler(graph, [3, 3], seed=0)
    for p in range(part.num_parts):
        lo, hi = int(part.bounds[p]), int(part.bounds[p + 1])
        mine = SEEDS[(SEEDS >= lo) & (SEEDS < hi)]
        if mine.size == 0:
            mine = np.array([lo], dtype=np.int32)
        a = ss.sample_for_shard(p, mine, batch_index=5, epoch=2)
        b = host.sample(mine, batch_index=5, epoch=2)
        assert len(a.blocks) == len(b.blocks)
        for ba, bb in zip(a.blocks, b.blocks):
            np.testing.assert_array_equal(ba.node_ids, bb.node_ids)
            np.testing.assert_array_equal(ba.dst_local, bb.dst_local)
            np.testing.assert_array_equal(ba.graph.src, bb.graph.src)
            np.testing.assert_array_equal(ba.graph.dst, bb.graph.dst)
            np.testing.assert_array_equal(ba.graph.etype, bb.graph.etype)
        np.testing.assert_array_equal(a.seed_perm, b.seed_perm)
    stats = ss.stats()
    assert stats["local_lookups"] + stats["halo_lookups"] > 0


# ---------------------------------------------------------------------------
# seed routing + batcher
# ---------------------------------------------------------------------------
def test_route_seeds_reconstructs_request_order(part):
    shard_seeds, mask, route = route_seeds(part, SEEDS)
    # the executor's gather: flat [P*b_max] outputs indexed by route must
    # give one row per request, dupes and order preserved
    np.testing.assert_array_equal(shard_seeds.reshape(-1)[route], SEEDS)
    assert mask.sum() == len(SEEDS)
    # pad slots are selection-inert stand-ins: the shard's first owned node
    owners = part.owner_of(SEEDS)
    for p in range(part.num_parts):
        n_owned_here = int((owners == p).sum())
        np.testing.assert_array_equal(
            shard_seeds[p, n_owned_here:], part.bounds[p])
        np.testing.assert_array_equal(mask[p], np.arange(
            shard_seeds.shape[1]) < n_owned_here)


def test_sharded_batcher_caches_recurring_batches(part):
    bat = ShardedBatcher(part, [3, 3], seed=0, tile=8, node_block=8)
    a = bat.build(SEEDS, step=0, epoch=0)
    b = bat.build(SEEDS, step=7, epoch=0)
    assert bat.host_builds == 1 and b.step == 7
    for ga, gb in zip(a.tensors, b.tensors):
        assert ga.src.shape == gb.src.shape
    # a new epoch re-keys the sampler stream: fresh neighborhoods, no replay
    bat.build(SEEDS, step=8, epoch=1)
    assert bat.host_builds == 2
    # stacked shard tensors: leading axis P, equal buckets across shards
    assert a.tensors[0].src.shape[0] == part.num_parts


# ---------------------------------------------------------------------------
# loader cache partitioning (satellite: shards sharing a process)
# ---------------------------------------------------------------------------
def test_loader_cache_keys_include_partition(graph, part):
    stream = SeedStream(graph.num_nodes, 8, seed=5, num_distinct=2)
    mk = lambda partition: MiniBatchLoader(  # noqa: E731
        FanoutSampler(graph, [3, 3], seed=0), stream, tile=8, node_block=8,
        bucket=True, num_batches=1, cache_blocks=4, partition=partition)
    l0, l1, l0b, ln = mk((part, 0)), mk((part, 1)), mk((part, 0)), mk(None)
    try:
        k0, k1 = l0._cache_key(SEEDS, None), l1._cache_key(SEEDS, None)
        assert k0 != k1, "two shards would replay each other's blocks"
        assert k0 == l0b._cache_key(SEEDS, None)
        assert ln._cache_key(SEEDS, None) != k0
    finally:
        for ld in (l0, l1, l0b, ln):
            ld.close()


def test_layout_cache_scoped_by_partition(graph):
    """A layout cache shared across shards must namespace entries: the same
    block signature under two scopes is two entries, not one replay."""
    seq = FanoutSampler(graph, [3, 3], seed=0).sample(SEEDS, batch_index=0)
    cache = LRUCache(16, name="shared")
    build_minibatch(seq, tile=8, node_block=8, bucket=True,
                    layout_cache=cache, layout_scope="shard0")
    misses_one_scope = cache.misses
    build_minibatch(seq, tile=8, node_block=8, bucket=True,
                    layout_cache=cache, layout_scope="shard0")
    assert cache.misses == misses_one_scope  # same scope: pure hits
    build_minibatch(seq, tile=8, node_block=8, bucket=True,
                    layout_cache=cache, layout_scope="shard1")
    assert cache.misses == 2 * misses_one_scope  # new scope: no replay


# ---------------------------------------------------------------------------
# engine config surface
# ---------------------------------------------------------------------------
def test_engine_config_dist_validation():
    with pytest.raises(ValueError):
        EngineConfig(model="rgat", dp=0)
    with pytest.raises(ValueError):
        EngineConfig(model="rgat", dp=2, partitions=3)
    cfg = EngineConfig(model="rgat", dp=2)
    assert cfg.num_partitions == 2 and cfg.distributed
    cfg = EngineConfig(model="rgat", dp=2, partitions=6)
    assert cfg.num_partitions == 6
    assert not EngineConfig(model="rgat").distributed


# ---------------------------------------------------------------------------
# dist executors vs the plain single-box executors (1 device, P=4)
# ---------------------------------------------------------------------------
def test_dist_serve_matches_plain_executor_bitwise(dist_engine, graph,
                                                   feats):
    eng = dist_engine
    params = eng.init_params(jax.random.key(0))
    seq = FanoutSampler(graph, [3, 3], seed=0).sample(SEEDS, batch_index=0,
                                                      epoch=0)
    mb = build_minibatch(seq, tile=8, node_block=8, bucket=True)
    ref = np.asarray(executor.BlockExecutor(eng.plans, backend="xla")
                     .run_minibatch(params, mb, feats))

    smb = eng.dist_batcher.build(SEEDS, step=0, epoch=0)
    got = np.asarray(eng.dist_serve_executor().run_minibatch(
        params, smb, eng.shard_features(np.asarray(feats))))
    np.testing.assert_array_equal(got, ref)   # bitwise, not approx


def test_dist_train_step_matches_plain_executor(dist_engine, graph, feats,
                                                labels):
    eng = dist_engine
    params = eng.init_params(jax.random.key(0))
    opt = AdamW(learning_rate=1e-2, weight_decay=0.01)
    seq = FanoutSampler(graph, [3, 3], seed=0).sample(SEEDS, batch_index=0,
                                                      epoch=0)
    mb = build_minibatch(seq, tile=8, node_block=8, bucket=True)
    s_ref, m_ref = executor.BlockTrainExecutor(eng.plans, opt) \
        .grad_and_update(opt.init(params), mb,
                         jnp.asarray(seq.slice_labels(labels)),
                         {"feature": feats[mb.input_ids]})

    smb = eng.dist_batcher.build(SEEDS, step=0, epoch=0)
    s_got, m_got = eng.dist_train_executor(opt).grad_and_update(
        opt.init(params), smb, labels,
        eng.shard_features(np.asarray(feats)))
    # the per-shard partial losses sum to the global mean exactly
    assert float(m_ref["loss"]) == float(m_got["loss"])
    assert float(m_ref["accuracy"]) == float(m_got["accuracy"])
    # gradients agree up to summation association (the all-reduce sums
    # per-shard partials; the plain step sums per-seed rows)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref.params),
                    jax.tree_util.tree_leaves(s_got.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_dist_trainer_loop_and_eval(dist_engine, graph, feats, labels):
    from repro.dist import DistTrainer
    eng = dist_engine
    ids = np.arange(0, 64, dtype=np.int32)
    tr = DistTrainer(eng, feats, labels, ids, val_ids=ids[:16], opt=None,
                     log=None)
    state = tr.init_state(eng.init_params(jax.random.key(0)))
    state, stats = tr.train(state, epochs=2, batch_size=16,
                            warmup_epochs=1)
    assert stats["steps"] == 8 and len(stats["losses"]) == 8
    assert np.isfinite(stats["final_loss"])
    assert stats["retraces_after_warmup"] == 0
    assert stats["num_partitions"] == 4 and stats["dp"] == 1
    ev = tr.evaluate(state.params, ids[:16], batch_size=16)
    assert np.isfinite(ev["loss"]) and 0.0 <= ev["accuracy"] <= 1.0


# ---------------------------------------------------------------------------
# multi-device: dp=4 == dp=1 bitwise (forced 4-device CPU subprocess)
# ---------------------------------------------------------------------------
def test_dp4_matches_dp1_bitwise():
    """Device-count invariance: folding 4 shards onto 1 device or spreading
    them 1-per-device changes nothing — serve logits, train loss, and the
    whole updated optimizer state are bitwise identical, because every
    reduction runs over the stacked [P, ...] axis in the same order."""
    stdout = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        assert jax.device_count() == 4, jax.devices()
        from repro.core.graph import synthetic_heterograph
        from repro.dist import (partition_graph, ShardedBatcher,
                                ShardedServeExecutor, ShardedTrainExecutor)
        from repro.launch.mesh import make_data_mesh
        from repro.optim import AdamW
        from repro.train import EngineConfig, RGNNEngine

        g = synthetic_heterograph(120, 900, 4, 7, seed=0)
        part = partition_graph(g, 4)
        SEEDS = np.array([3, 50, 7, 3, 119, 0, 88, 12], dtype=np.int32)
        eng = RGNNEngine(g, EngineConfig(
            model="rgat", layers=2, dim=16, hidden=12, classes=6,
            fanouts=[3, 3], tile=8, node_block=8, seed=0))
        rng = np.random.default_rng(1)
        feats = np.asarray(rng.normal(size=(g.num_nodes, 16)), np.float32)
        labels = np.asarray(rng.integers(0, 6, g.num_nodes))
        params = eng.init_params(jax.random.key(0))
        own = jnp.asarray(part.shard_features(feats))
        smb = ShardedBatcher(part, [3, 3], seed=0, tile=8,
                             node_block=8).build(SEEDS, step=0, epoch=0)
        opt = AdamW(learning_rate=1e-2, weight_decay=0.01)
        out = {}
        for dp in (1, 4):
            mesh = make_data_mesh(dp)
            logits = np.asarray(ShardedServeExecutor(eng.plans, mesh)
                                .run_minibatch(params, smb, own))
            st, m = ShardedTrainExecutor(eng.plans, opt, mesh) \\
                .grad_and_update(opt.init(params), smb, labels, own)
            leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
                (st.params, st.mu, st.nu))]
            out[dp] = (logits, float(m["loss"]), leaves)
        assert (out[1][0] == out[4][0]).all(), "serve logits differ"
        assert out[1][1] == out[4][1], "loss differs"
        assert all((a == b).all() for a, b in zip(out[1][2], out[4][2])), \\
            "optimizer state differs"
        print("bitwise-ok")
        """, devices=4)
    assert "bitwise-ok" in stdout


def test_data_mesh_and_elastic_shrink():
    """make_data_mesh over forced CPU devices + the data-only elastic
    branch: losing a device shrinks dp while logical shards refold."""
    stdout = run_sub("""
        import jax
        from repro.launch.mesh import make_data_mesh, plan_elastic_mesh
        m = make_data_mesh()
        assert m.devices.shape == (4,) and m.axis_names == ("data",)
        m2 = make_data_mesh(2)
        assert m2.devices.shape == (2,)
        plan = plan_elastic_mesh(3, model_parallel=1, data_only=True)
        assert plan.shape == (3,) and plan.axes == ("data",)
        assert plan.dp_degree == 3 and plan.dropped_devices == 0
        # the default planner keeps the trailing model axis alive
        lm = plan_elastic_mesh(3, model_parallel=1)
        assert lm.shape == (3, 1) and lm.axes == ("data", "model")
        m3 = make_data_mesh(plan.shape[0], devices=jax.devices()[:3])
        assert m3.devices.shape == (3,)
        print("mesh-ok")
        """, devices=4)
    assert "mesh-ok" in stdout


def test_rgnn_hector_shards_over_mesh():
    """The generated RGNN code compiles and runs with node features sharded
    over the data axis of a 2x4 mesh, matching the unsharded forward."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.graph import synthetic_heterograph
        from repro.core.module import HectorModule
        from repro.models import rgat_program
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        hg = synthetic_heterograph(256, 2000, 3, 6, seed=0)
        mod = HectorModule(rgat_program(16, 16), hg, jit=False)
        params = mod.init(jax.random.key(0))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(hg.num_nodes, 16)), jnp.float32)
        fn = jax.jit(
            lambda p, f: mod.apply(p, {'feature': f})['h_out'],
            in_shardings=(None, NamedSharding(mesh, P('data', None))))
        out = fn(params, x)
        ref = mod.apply(params, {'feature': x})['h_out']
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print('ok')
        """)
