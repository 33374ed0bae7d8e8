"""Cached serving over repeat traffic: the steady state the caches exist for.

The serving driver runs a seed stream that cycles over ``DISTINCT``
batches with the sampled-block LRU and the KernelLayouts LRU on. After
warmup the compiled executor never retraces, each shape bucket traces
once, and every repeated batch is served from the block cache (no
host-side sampling or layout rebuild). All counters are read from the
run's metrics-registry snapshot, the one telemetry surface for cache and
trace counts. Each paper model runs the same contract.
"""
import pytest

from repro.launch.serve_rgnn import serve
from repro.obs.registry import snapshot_counter_total, snapshot_value

# one bucketed shape set, small enough for Pallas interpret mode
CONFIG = dict(
    dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], batch_size=8, tile=8, node_block=8,
    bucket=True, seed=0, backend="pallas_interpret",
    cache_blocks=32, cache_layouts=128,
)
DISTINCT = 3          # distinct seed batches the stream cycles over
NUM_BATCHES = 12
N_REPEATS = NUM_BATCHES - DISTINCT


@pytest.fixture(scope="module", params=["rgat", "rgcn", "hgt"])
def cached(request):
    stats = serve(model=request.param, num_batches=NUM_BATCHES,
                  repeat_after=DISTINCT, log=lambda *a, **k: None, **CONFIG)
    m = stats["metrics"]
    return stats, snapshot_counter_total(m, "executor_traces"), m


def test_zero_retraces_after_warmup(cached):
    stats, _, _ = cached
    assert stats["retraces_after_warmup"] == 0, (
        f"executor retraced {stats['retraces_after_warmup']}x after "
        f"warmup (expected 0)")


def test_one_trace_per_compiled_bucket(cached):
    stats, traces, _ = cached
    assert traces == stats["executor_compiled"], (
        f"trace count {traces} != compiled entries "
        f"{stats['executor_compiled']} (each bucket must trace once)")


def test_traces_bounded_by_distinct_batches(cached):
    _, traces, _ = cached
    assert traces <= DISTINCT, (
        f"{traces} traces for {DISTINCT} distinct batches "
        f"(bucketing regressed)")


def test_block_cache_misses_once_per_distinct_batch(cached):
    _, _, m = cached
    misses = snapshot_value(m, "loader_cache_misses", cache="block_cache")
    assert (misses or 0) == DISTINCT, (
        f"{misses} block-cache misses for {DISTINCT} distinct batches")


def test_every_repeat_served_from_block_cache(cached):
    _, _, m = cached
    hits = snapshot_value(m, "loader_cache_hits", cache="block_cache")
    assert (hits or 0) == N_REPEATS, (
        f"{hits} block-cache hits, expected {N_REPEATS} "
        f"(a repeat rebuilt its layouts host-side)")


def test_hit_rate_gauge_matches_repeats(cached):
    """The registry carries the rate gauge itself, not only raw counters."""
    _, _, m = cached
    rate = snapshot_value(m, "loader_cache_hit_rate", cache="block_cache")
    want = N_REPEATS / NUM_BATCHES
    assert abs((rate or 0.0) - want) <= 1e-9, (
        f"loader_cache_hit_rate gauge {rate} != expected {want:.3f} for "
        f"{N_REPEATS}/{NUM_BATCHES} repeats")
