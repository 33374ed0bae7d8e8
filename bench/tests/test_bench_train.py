"""The full-graph training cell end to end on the CPU at a tiny size
(interpret-mode kernels), through the runner the chip runs; and the check
that decides ``correct`` seen to fail with the timed path broken
underneath, and with the control (the reference at the precision below the
configuration's) in the program's place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
from bench.runners import common, train_full


def test_train_full_cell_is_correct(tmp_path):
    out = tiny.run(tiny.cell("rgat-am.train_full", tmp_path))
    assert tiny.correct(out), out.checks
    assert out.attempted >= 1 and out.failed == 0
    assert out.metrics["full_step_ms"] > 0 and out.metrics["setup_s"] > 0
    assert out.window_compiles == 0



# --- faults in the timed path: each must make ``correct`` false ----------
def _state_unchanged(monkeypatch):
    from repro.core.executor import StackTrainExecutor
    orig = StackTrainExecutor.grad_and_update

    def step(self, state, *args):
        keep = jax.tree.map(jnp.copy, state)
        _, metrics = orig(self, state, *args)
        return keep, metrics
    monkeypatch.setattr(StackTrainExecutor, "grad_and_update", step)


def _half_batch(monkeypatch):
    from repro.train import trainer
    orig = trainer.FullGraphTrainer.__init__

    def init(self, engine, feats, labels, train_ids, **kw):
        ids = np.asarray(train_ids)
        orig(self, engine, feats, labels, ids[: ids.size // 2], **kw)
    monkeypatch.setattr(trainer.FullGraphTrainer, "__init__", init)


def _loss_altered(monkeypatch):
    from repro.core import executor
    orig = executor.softmax_xent

    def xent(logits, labels):
        loss, acc = orig(logits, labels)
        return loss * 1.001, acc
    monkeypatch.setattr(executor, "softmax_xent", xent)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _loss_altered])
def test_train_fault_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = tiny.run(tiny.cell("rgat-am.train_full", tmp_path))
    assert not tiny.correct(out), out.checks



# --- the control: the reference one precision down, in the program's place
def test_train_control_is_not_correct(tmp_path):
    """The control fails the cell's limits at the cell's own size on the
    chip (``bench/calibrate.py``); at a size a test holds, the error of
    three bfloat16 passes is smaller, so here "high" must read well above
    the program, and one bfloat16 pass must fail the limits outright."""
    c = tiny.cell("rgat-am.train_full", tmp_path)
    jax.config.update("jax_default_matmul_precision", "highest")
    arrays = common.load_arrays(c)
    steps = int(c.traffic["first_steps"])
    prog = train_full.Program(c, arrays)
    first = prog.first_steps(steps)
    prog.close()
    ref = train_full.reference(c, arrays, steps)
    program = train_full.compare(first, ref)
    high = train_full.compare(
        train_full.reference(c, arrays, steps, precision="high"), ref)
    assert high["grad_gap"] > 10 * program["grad_gap"], (high, program)
    bf16 = train_full.compare(
        train_full.reference(c, arrays, steps, precision="bfloat16"), ref)
    assert any(bf16[k] > c.limit(k) for k in train_full.CHECKED), bf16
    same = train_full.compare(train_full.reference(c, arrays, steps), ref)
    assert all(same[k] == 0 for k in train_full.CHECKED), same
