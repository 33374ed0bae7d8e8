"""Optimizer, schedule, checkpointing and elastic mesh planning."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.optim import AdamW, cosine_schedule
from repro.launch.mesh import plan_elastic_mesh


# --------------------------------------------------------------- optimizer
def test_adamw_optimizes_quadratic():
    opt = AdamW(learning_rate=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = opt.init(params)

    @jax.jit
    def step(state):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(state.params)
        return opt.update(g, state)

    for _ in range(120):
        state = step(state)
    assert float(jnp.max(jnp.abs(state.params["w"]))) < 0.15


def test_adamw_clips_global_norm():
    opt = AdamW(learning_rate=0.0, clip_norm=1.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    g = {"w": jnp.asarray([1e6, 0.0, 0.0])}
    new = opt.update(g, state)
    # lr=0: params unchanged; moments reflect clipped gradient
    assert float(jnp.max(new.mu["w"])) <= 0.11


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup_steps=10, total_steps=100)
    assert float(lr(jnp.int32(0))) == 0.0
    assert abs(float(lr(jnp.int32(10))) - 1e-3) < 1e-9
    assert float(lr(jnp.int32(100))) < 2e-4
    assert float(lr(jnp.int32(100))) >= 1e-4 - 1e-9   # floor


# --------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    ck.save(5, tree, blocking=True)
    out = ck.restore(tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])


def test_checkpoint_async_and_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"w": jnp.zeros(4)}
    for s in (1, 2, 3, 4):
        ck.save(s, jax.tree.map(lambda x: x + s, tree))
    ck.wait()
    assert ck.steps() == [3, 4]
    out = ck.restore(tree)          # latest
    np.testing.assert_allclose(out["w"], np.full(4, 4.0))


def test_checkpoint_atomicity_tmp_never_visible(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(7, {"w": jnp.ones(2)}, blocking=True)
    names = [p.name for p in tmp_path.iterdir()]
    assert "step_00000007" in names
    assert not any(n.endswith(".tmp") for n in names)


# --------------------------------------------------------------- mesh
def test_elastic_plan_preserves_tp():
    plan = plan_elastic_mesh(512 - 16, model_parallel=16)
    assert plan.shape[-1] == 16
    assert plan.used_devices == 496
    assert plan.dropped_devices == 0
    plan2 = plan_elastic_mesh(509, model_parallel=16)
    assert plan2.used_devices == 496 and plan2.dropped_devices == 13
    with pytest.raises(ValueError):
        plan_elastic_mesh(15, model_parallel=16)
