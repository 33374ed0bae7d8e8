"""Sampled training through the driver: the steady-state training contract.

After the warmup epoch the compiled train step retraces zero times across
two further epochs (shape-bucketed mini-batches all hit the executor
compile cache), each bucket traces once, and the loss falls. Each paper
model runs the same contract. That the same seed batch draws fresh blocks
each epoch is pinned in ``tests/test_sampling.py``.
"""
import pytest

from repro.launch.train_rgnn import train

CONFIG = dict(
    dataset="synthetic", scale=0.05, layers=2, dim=16,
    hidden=16, classes=6, fanouts=[3, 3], batch_size=32, epochs=3,
    lr=1e-2, tile=8, node_block=8, bucket=True, seed=0, val_frac=0.0,
    eval_every_epochs=0, backend="xla",
)


@pytest.fixture(scope="module", params=["rgat", "rgcn", "hgt"])
def stats(request):
    return train(model=request.param, log=lambda *a, **k: None, **CONFIG)


def test_two_epochs_after_warmup(stats):
    warm_epochs = stats["warmup_steps"] // stats["batches_per_epoch"]
    assert stats["epochs"] - warm_epochs >= 2, (
        "config must leave >= 2 epochs after warmup")


def test_zero_retraces_after_warmup(stats):
    assert stats["retraces_after_warmup"] == 0, (
        f"train step retraced {stats['retraces_after_warmup']}x after the "
        f"warmup epoch (expected 0 across two epochs)")


def test_one_trace_per_compiled_bucket(stats):
    assert stats["executor_traces"] == stats["executor_compiled"], (
        f"trace count {stats['executor_traces']} != compiled entries "
        f"{stats['executor_compiled']} (each bucket must trace once)")


def test_loss_decreases(stats):
    losses = stats["losses"]
    assert losses[-1] < losses[0], (
        f"loss did not decrease: {losses[0]:.4f} -> {losses[-1]:.4f}")
