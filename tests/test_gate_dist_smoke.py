"""Data-parallel serving and training through the drivers.

Multi-shard serving over repeat traffic replays its compiled
``shard_map`` step after warmup and reuses the sharded batcher's cached
batches; the data-parallel training loop lowers its loss. A child process
with four forced host devices checks that the halo-feature all-gather and
the gradient all-reduce live inside the one compiled train step, and
that repeat steps stay on one cache entry. That dp=4 is bitwise equal to
dp=1, and that the trainer does not retrace after its warmup epoch, is
pinned in ``tests/test_dist.py``.
"""
import json

import numpy as np
import pytest

import jax

from repro.core.graph import synthetic_heterograph
from repro.dist import DistTrainer
from repro.launch.serve_rgnn import serve
from repro.train import EngineConfig, RGNNEngine

from test_dist import run_sub

# multi-shard serving over repeat traffic (dp=1: 4 logical shards folded
# onto the one real device; the shard_map program is identical at dp=4)
SERVE_CONFIG = dict(
    model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], batch_size=8, num_batches=9, tile=8,
    node_block=8, repeat_after=3, seed=0, partitions=4, obs_mode="off",
)

# data-parallel training loop: 64 seeds x batch 16 over 2 epochs; epoch 1
# is warmup (traces every shuffled bucket combination)
TRAIN_CONFIG = dict(num_ids=64, batch_size=16, epochs=2, warmup_epochs=1)

# the fused step at dp=4: the collectives must live inside the one lowered
# module, and repeat steps must stay on one cache entry
_DP4_CODE = """
    import json
    import numpy as np, jax, jax.numpy as jnp
    assert jax.device_count() == 4, jax.devices()
    from repro.core.graph import synthetic_heterograph
    from repro.dist import (partition_graph, ShardedBatcher,
                            ShardedTrainExecutor)
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
    tr = ShardedTrainExecutor(eng.plans, opt, make_data_mesh(4))
    hlo = tr.lowered_hlo(opt.init(params), smb, labels, own)
    state = opt.init(params)
    for _ in range(3):
        state, _m = tr.grad_and_update(state, smb, labels, own)
    print(json.dumps({
        "hlo_all_gathers": hlo.count("all_gather"),
        "train_compiled": tr.num_compiled,
        "train_cache_hits": tr.cache_hits,
    }))
"""


@pytest.fixture(scope="module")
def served():
    return serve(log=lambda *a, **k: None, **SERVE_CONFIG)


@pytest.fixture(scope="module")
def trained():
    g = synthetic_heterograph(120, 900, 4, 7, seed=0)
    eng = RGNNEngine(g, EngineConfig(
        model="rgat", layers=2, dim=16, hidden=12, classes=6,
        fanouts=[3, 3], tile=8, node_block=8, seed=0, partitions=4))
    rng = np.random.default_rng(1)
    feats = np.asarray(rng.normal(size=(g.num_nodes, 16)), np.float32)
    labels = np.asarray(rng.integers(0, 6, g.num_nodes))
    ids = np.arange(0, TRAIN_CONFIG["num_ids"], dtype=np.int32)
    tr = DistTrainer(eng, feats, labels, ids, log=None)
    state = tr.init_state(eng.init_params(jax.random.key(0)))
    _, stats = tr.train(state, epochs=TRAIN_CONFIG["epochs"],
                        batch_size=TRAIN_CONFIG["batch_size"],
                        warmup_epochs=TRAIN_CONFIG["warmup_epochs"])
    return stats


@pytest.fixture(scope="module")
def dp4():
    return json.loads(run_sub(_DP4_CODE, devices=4).strip().splitlines()[-1])


def test_multi_shard_serve_zero_retraces_after_warmup(served):
    assert served["retraces_after_warmup"] == 0, (
        f"multi-shard serve retraced {served['retraces_after_warmup']} "
        f"times after warmup (want 0)")


def test_sharded_batcher_reuses_repeat_batches(served):
    assert served["batcher_batch_cache"]["hits"] > 0, (
        "sharded batcher never reused a cached batch on repeat traffic")


def test_data_parallel_loss_decreases(trained):
    losses = trained["losses"]
    assert losses[-1] < losses[0], (
        f"train loss did not decrease ({losses[0]:.4f} -> "
        f"{losses[-1]:.4f})")


def test_collectives_fused_into_train_step(dp4):
    assert dp4["hlo_all_gathers"] >= 2, (
        f"lowered train step contains {dp4['hlo_all_gathers']} all_gather "
        f"collectives (want >=2: halo features + gradient all-reduce fused "
        f"into the one compiled module)")


def test_dp4_train_step_served_from_one_entry(dp4):
    assert dp4["train_compiled"] == 1 and dp4["train_cache_hits"] >= 2, (
        f"dp=4 train step not served from one compiled entry "
        f"(compiled={dp4['train_compiled']}, "
        f"hits={dp4['train_cache_hits']})")
