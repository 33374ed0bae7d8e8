"""Device-native sampling: a steady state with zero host pipeline work.

Repeat traffic is served through both sampling pipelines. With
``sampler="device"`` every non-cached batch is built by the jit sampling
and layout programs: no host build, no sampler or executor retrace after
warmup, no blocking count readback and no bucket overflow. Both pipelines
draw the same counter-based selection stream, so they predict alike.
"""
import pytest

from repro.launch.serve_rgnn import serve

CONFIG = dict(
    model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], batch_size=8, num_batches=12, tile=8,
    node_block=8, repeat_after=3, seed=0,
    # two full repeat cycles of warmup: the first cycle traces the
    # worst-case buckets, the second traces the shrunken ones after the
    # non-blocking count drains land
    warmup_batches=6,
)


@pytest.fixture(scope="module")
def runs():
    quiet = lambda *a, **k: None  # noqa: E731
    return (serve(sampler="device", log=quiet, **CONFIG),
            serve(sampler="host", log=quiet, **CONFIG))


def test_device_serve_zero_host_builds(runs):
    d, _ = runs
    assert d["host_builds"] == 0, (
        f"device serve ran {d['host_builds']} host pipeline builds (want 0)")


def test_device_serve_builds_on_device(runs):
    d, _ = runs
    assert d["device_builds"] > 0, "device serve built no batches on device"


def test_sampler_zero_retraces_after_warmup(runs):
    d, _ = runs
    n = d.get("sampler_retraces_after_warmup", 0)
    assert n == 0, f"device sampler retraced {n} times after warmup"


def test_executor_zero_retraces_after_warmup(runs):
    d, _ = runs
    assert d["retraces_after_warmup"] == 0, (
        f"block executor retraced {d['retraces_after_warmup']} times after "
        f"warmup of the device stream")


def test_sampler_zero_count_syncs(runs):
    d, _ = runs
    n = d.get("sampler_count_syncs", 0)
    assert n == 0, (
        f"device sampler blocked on {n} count readbacks "
        f"(want a sync-free loop)")


def test_sampler_zero_bucket_overflows(runs):
    d, _ = runs
    n = d.get("sampler_bucket_overflows", 0)
    assert n == 0, (
        f"{n} stage-B bucket overflows (shrunken guess truncated a batch)")


def test_device_and_host_predict_alike(runs):
    d, h = runs
    assert d["last_preds"].tolist() == h["last_preds"].tolist(), (
        "device and host pipelines predicted differently on the same seed "
        "stream")
