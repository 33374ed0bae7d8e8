"""The autotuner's persistent cache: tune once cold, rerun warm.

The serving driver runs twice with ``tune="full"`` against one cache file
under the test's own temporary directory. The cold run measures; the warm
run replays every decision from the cache with zero on-device
measurements, and serves its steady state with zero retraces after warmup
(the decision table is part of the executor compile-cache key). Counts
are read from the runs' metrics-registry snapshots.
"""
import pytest

from repro.core.graph import CPU_REDUCED_SCALES
from repro.launch.serve_rgnn import serve
from repro.obs.registry import snapshot_counter_total as total
from repro.tune.cache import CACHE_ENV

CONFIG = dict(
    model="rgat", dataset="aifb", scale=CPU_REDUCED_SCALES["aifb"],
    layers=2, dim=32, hidden=32, classes=8, batch_size=16,
    num_batches=6, repeat_after=2, cache_blocks=8, cache_layouts=32,
    tune="full",
)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tune") / "tune.json")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(CACHE_ENV, path)
        kw = dict(CONFIG, tune_cache=path, log=lambda *a, **k: None)
        cold = serve(**kw)
        warm = serve(**kw)
    return cold, warm


def test_cold_run_measures(runs):
    cold, _ = runs
    assert total(cold["metrics"], "tune_measurements") > 0, (
        f"cold tuning measured nothing: {cold['tune_measurements']}")


def test_warm_run_measures_nothing(runs):
    _, warm = runs
    n = total(warm["metrics"], "tune_measurements")
    assert n == 0, f"warm run re-measured despite persistent cache: {n}"


def test_warm_run_replays_every_decision(runs):
    cold, warm = runs
    replays = total(warm["metrics"], "tune_cache_hits")
    assert replays >= cold["tune_tuned_ops"], (
        f"warm run replayed {replays} decisions for "
        f"{cold['tune_tuned_ops']} tuned ops")


def test_warm_run_zero_retraces_after_warmup(runs):
    _, warm = runs
    assert warm["retraces_after_warmup"] == 0, (
        f"tuned serving retraced after warmup: "
        f"{warm['retraces_after_warmup']}")
