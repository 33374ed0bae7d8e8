"""Tiered feature storage under Zipf-skewed traffic.

One seeded stream of Zipf(1.5) batches goes through the three feature
tiers (device, host, cached). With a quarter-table budget the cached
tier's hot-row cache absorbs most reads, never retraces its gather
programs after warmup, and moves fewer host bytes than the host tier,
while all three tiers return the same rows. A fully-hot batch does no
host work, and a forced tiny budget bounds device memory below the
table. The table is wide (1024 columns) so that data movement dominates,
the regime the cache exists for. Gather times are not asserted here.
"""
import numpy as np
import pytest

from repro.core.graph import synthetic_heterograph
from repro.feats import make_feature_store
from repro.sampling import SeedStream

CONFIG = dict(num_nodes=16000, num_edges=64000, num_ntypes=4, num_etypes=8,
              seed=0, target_compaction=0.5)
DIM = 1024
BATCH = 1024
WARMUP = 8
STEADY = 40
ALPHA = 1.5
TIERS = ("device", "host", "cached")


@pytest.fixture(scope="module")
def data():
    graph = synthetic_heterograph(**CONFIG)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(graph.num_nodes, DIM)).astype(np.float32)
    stream = SeedStream(graph.num_nodes, BATCH, seed=7, zipf_alpha=ALPHA)
    batches = [stream.batch(t) for t in range(WARMUP + STEADY)]
    return graph, feats, batches


@pytest.fixture(scope="module")
def tiers(data):
    """Warm every tier, then run the steady batches through each; returns
    the stores, each tier's checksum of the steady batches' rows, and the
    cached tier's steady-state hit rate and retraces."""
    graph, feats, batches = data
    stores = {
        kind: make_feature_store(
            feats, graph, kind=kind,
            budget=graph.num_nodes // 4 if kind == "cached" else None)
        for kind in TIERS}
    for step, ids in enumerate(batches[:WARMUP]):
        for store in stores.values():
            store.gather(ids, step=step)
    cached = stores["cached"]
    warm = (cached.trace_count, cached.hits, cached.misses)
    sums = {kind: 0.0 for kind in TIERS}
    for step, ids in enumerate(batches[WARMUP:], start=WARMUP):
        for kind, store in stores.items():
            out = store.gather(ids, step=step)["feature"]
            sums[kind] += float(np.asarray(out).sum())
    hits, misses = cached.hits - warm[1], cached.misses - warm[2]
    return {"stores": stores, "sums": sums,
            "hit_rate": hits / max(hits + misses, 1),
            "retraces": cached.trace_count - warm[0]}


def test_cached_steady_hit_rate(tiers):
    rate = tiers["hit_rate"]
    assert rate >= 0.6, (
        f"steady-state hit rate {rate:.2f} < 0.60 under Zipf({ALPHA}) "
        f"traffic")


def test_cached_zero_retraces_after_warmup(tiers):
    n = tiers["retraces"]
    assert n == 0, f"{n} gather-program retraces after warmup (expected 0)"


def test_tiers_return_identical_rows(tiers):
    sums = tiers["sums"]
    assert sums["device"] == sums["host"] == sums["cached"], (
        f"tier checksums diverge: {sums}")


def test_cached_moves_fewer_host_bytes_than_host_tier(tiers):
    stores = tiers["stores"]
    cached, host = stores["cached"].bytes_moved, stores["host"].bytes_moved
    assert cached < host, (
        f"cached tier moved {cached} host bytes >= host tier's {host}")


def test_fully_hot_batch_touches_no_host_table(data):
    graph, feats, batches = data
    hot = make_feature_store(feats, graph, kind="cached",
                             budget=graph.num_nodes)
    ids = batches[0]
    hot.gather(ids, step=0)
    g0, b0 = hot.host_gathers, hot.bytes_moved
    np.testing.assert_array_equal(
        np.asarray(hot.gather(ids, step=1)["feature"]), feats[ids])
    assert hot.host_gathers == g0 and hot.bytes_moved == b0, (
        f"a fully-hot batch touched the host tables "
        f"({hot.host_gathers - g0} gathers, {hot.bytes_moved - b0} bytes)")


@pytest.fixture(scope="module")
def tiny(data):
    """A forced 64-row budget over the first four batches."""
    graph, feats, batches = data
    store = make_feature_store(feats, graph, kind="cached", budget=64)
    for step, ids in enumerate(batches[:4]):
        np.testing.assert_array_equal(
            np.asarray(store.gather(ids, step=step)["feature"]), feats[ids])
    return store


def test_tiny_budget_bounds_device_memory(tiny):
    assert tiny.device_bytes() < tiny.table_bytes, (
        f"tiny-budget device bytes {tiny.device_bytes()} not below table "
        f"bytes {tiny.table_bytes}")


def test_tiny_budget_overflows(tiny):
    assert tiny.overflows > 0, (
        "forced tiny budget produced no overflows (the overflow path is "
        "not exercised)")
