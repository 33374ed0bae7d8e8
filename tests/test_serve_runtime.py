"""Online serving runtime tests: open-loop load determinism, deadline-aware
coalescing, the async request pipeline (graceful shutdown, no orphaned
threads), and multi-model tenancy isolation."""
import threading
import time

import numpy as np
import pytest
import jax

import hector
from repro.core.graph import synthetic_heterograph
from repro.serve import (LATE, OK, REJECTED_DEADLINE, REJECTED_OVERLOAD,
                         REJECTED_SHUTDOWN, Coalescer, LatencyModel,
                         MultiTenantRuntime, OpenLoopLoad, Request,
                         ServingRuntime, ladder)


# ---------------------------------------------------------------------------
# open-loop load generation
# ---------------------------------------------------------------------------
def test_ladder_rung_sets():
    assert ladder(16, "pow2") == [1, 2, 4, 8, 16]
    assert ladder(16, "fine") == [1, 2, 3, 4, 6, 8, 12, 16]
    assert ladder(5, "fine") == [1, 2, 3, 4, 6, 8]   # top rounds up to pow2
    with pytest.raises(ValueError):
        ladder(0)
    with pytest.raises(ValueError):
        ladder(8, "coarse")


@pytest.mark.parametrize("process", ["poisson", "burst", "uniform"])
def test_open_loop_schedule_deterministic(process):
    """The schedule is a pure function of the seed: same args -> identical
    requests (arrivals, seeds, sizes, SLOs); a different seed differs."""
    mk = lambda s: OpenLoopLoad(500, rate_rps=200.0, num_requests=24,
                                process=process, size_choices=(1, 2, 4),
                                slo_ms=(20.0, 50.0), seed=s)
    a, b = mk(3).requests(), mk(3).requests()
    assert len(a) == len(b) == 24
    for ra, rb in zip(a, b):
        assert ra.arrival_s == rb.arrival_s
        assert ra.slo_ms == rb.slo_ms
        np.testing.assert_array_equal(ra.seeds, rb.seeds)
    arr = np.array([r.arrival_s for r in a])
    assert np.all(np.diff(arr) >= 0)            # arrivals are sorted
    c = mk(4).requests()
    assert any(ra.arrival_s != rc.arrival_s or
               not np.array_equal(ra.seeds, rc.seeds)
               for ra, rc in zip(a, c))


def test_open_loop_burst_groups_and_tenant_routing():
    load = OpenLoopLoad(100, rate_rps=100.0, num_requests=12,
                        process="burst", burst_size=3, slo_ms=10.0,
                        models=("a", "b"), seed=0)
    reqs = load.requests()
    arr = [r.arrival_s for r in reqs]
    # bursts arrive back-to-back in groups of burst_size
    assert arr[0] == arr[1] == arr[2]
    assert arr[3] == arr[4] == arr[5] != arr[2]
    assert [r.model for r in reqs[:4]] == ["a", "b", "a", "b"]


# ---------------------------------------------------------------------------
# deadline-aware coalescing (unit level: no engine, synthetic clock)
# ---------------------------------------------------------------------------
def _req(rid, size=1, slo_ms=100.0, t_arrive=0.0):
    r = Request(rid=rid, seeds=np.arange(size, dtype=np.int32),
                arrival_s=0.0, slo_ms=slo_ms)
    r.t_arrive = t_arrive
    return r


def _model(table):
    lm = LatencyModel(headroom=1.0)
    for rung, ms in table.items():
        lm.calibrate(rung, ms)
    return lm


def test_coalescer_picks_largest_feasible_rung():
    """Admission merges into the largest rung whose *measured* latency
    meets the tightest in-batch deadline — not simply the largest rung."""
    lm = _model({1: 1.0, 2: 2.0, 4: 4.0, 8: 50.0})
    co = Coalescer([1, 2, 4, 8], lm, max_wait_ms=5.0)
    # 6 single-seed requests, 10 ms budget: rung 8 (50 ms) is infeasible,
    # rung 4 (4 ms) fits -> admit exactly 4 requests at rung 4
    pending = [_req(i, slo_ms=10.0) for i in range(6)]
    d = co.plan(pending, now=0.0)
    assert d.batch is not None and d.batch.rung == 4
    assert [r.rid for r in d.batch.requests] == [0, 1, 2, 3]
    assert len(pending) == 2 and not d.rejects
    assert d.batch.seeds.shape == (4,)
    assert d.batch.slices == [(0, 1), (1, 2), (2, 3), (3, 4)]


def test_coalescer_rejects_expired_never_serves_late_silently():
    lm = _model({1: 5.0, 8: 10.0})
    co = Coalescer([1, 8], lm, max_wait_ms=1.0)
    pending = [
        _req(0, slo_ms=100.0),                 # healthy
        _req(1, slo_ms=10.0, t_arrive=-1.0),   # deadline already passed
        _req(2, slo_ms=4.0),                   # slack < smallest-rung est
    ]
    d = co.plan(pending, now=0.0, drain=True)
    assert sorted(r.rid for r in d.rejects) == [1, 2]
    assert d.batch is not None
    assert [r.rid for r in d.batch.requests] == [0]


def test_coalescer_waits_for_fill_then_drain_flushes():
    """With loose deadlines and a part-filled rung the coalescer holds for
    more arrivals; drain (shutdown) admits immediately."""
    lm = _model({1: 1.0, 2: 1.5, 4: 2.0})
    co = Coalescer([1, 2, 4], lm, max_wait_ms=50.0)
    pending = [_req(0, slo_ms=10_000.0)]
    d = co.plan(pending, now=0.0)
    assert d.batch is None and not d.rejects and d.wait_s > 0
    assert len(pending) == 1
    d = co.plan(pending, now=0.0, drain=True)
    assert d.batch is not None and d.batch.requests[0].rid == 0
    assert d.batch.rung == 1                    # covering rung, minimal pad
    assert not pending


def test_coalescer_padding_repeats_first_seed():
    lm = _model({4: 1.0})
    co = Coalescer([4], lm, max_wait_ms=0.0)
    pending = [_req(0, size=3, slo_ms=100.0)]
    d = co.plan(pending, now=0.0, drain=True)
    np.testing.assert_array_equal(d.batch.seeds, np.array([0, 1, 2, 0]))


def test_latency_model_jumps_up_decays_down():
    lm = LatencyModel(alpha=0.5, headroom=1.0)
    lm.calibrate(4, 10.0)
    lm.observe(4, 40.0)
    assert lm.estimate(4) == 40.0               # spikes register instantly
    lm.observe(4, 10.0)
    assert 10.0 < lm.estimate(4) < 40.0         # recovery is gradual
    # unmeasured rung falls back to the nearest measured rung above
    lm.calibrate(16, 100.0)
    assert lm.estimate(8) == 100.0


# ---------------------------------------------------------------------------
# the async runtime end-to-end (small compiled engine)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    graph = synthetic_heterograph(num_nodes=160, num_edges=900, num_ntypes=3,
                                  num_etypes=4, seed=0)
    engine = hector.compile("rgat", graph, layers=1, dim=8, hidden=8,
                            classes=4, sample=2, tile=8, node_block=8,
                            bucket=True, seed=0)
    params = engine.init(jax.random.key(0))
    feats = np.random.default_rng(1).normal(
        size=(graph.num_nodes, 8)).astype(np.float32)
    store = engine.make_feature_store(feats)
    return graph, engine, params, store


def _runtime(served, **kw):
    graph, engine, params, store = served
    kw.setdefault("rungs", ladder(4, "fine"))
    kw.setdefault("max_wait_ms", 2.0)
    return ServingRuntime(engine, params, store, **kw)


def _calibrate(rt):
    rt.calibrate(batches_per_rung=1, validate=False, iters=1,
                 probe_batches=4, warm_rounds=2)


def test_runtime_end_to_end_all_ok_zero_retraces(served):
    graph = served[0]
    rt = _runtime(served)
    try:
        _calibrate(rt)
        load = OpenLoopLoad(graph.num_nodes, rate_rps=400.0,
                            num_requests=16, size_choices=(1, 2, 4),
                            slo_ms=30_000.0, seed=2)
        handles = [rt.submit(r) for r in load.requests()]
        rt.drain(timeout=60.0)
        for h in handles:
            resp = h.wait(timeout=10.0)
            assert resp is not None and resp.status == OK
            assert resp.logits.shape[1] == 4
            assert np.all(np.isfinite(resp.logits))
            assert resp.latency_ms >= resp.queue_ms >= 0.0
        s = rt.stats()
        assert s["requests"] == 16 and s["by_status"] == {OK: 16}
        assert s["slo_attainment"] == 1.0
        assert s["retraces_after_warmup"] == 0
        # a floor growth without a retrace is benign (the grown bucket was
        # already compiled); with the short probe pass used here allow one
        assert s["shape_floor_growths"] <= 1
    finally:
        rt.close()


def test_runtime_response_sizes_match_requests(served):
    graph = served[0]
    rt = _runtime(served)
    try:
        _calibrate(rt)
        sizes = [1, 3, 2, 4]
        handles = [
            rt.submit(Request(rid=i, seeds=np.arange(sz, dtype=np.int32),
                              arrival_s=0.0, slo_ms=30_000.0))
            for i, sz in enumerate(sizes)]
        rt.drain(timeout=60.0)
        for sz, h in zip(sizes, handles):
            resp = h.wait(timeout=10.0)
            assert resp.status == OK and resp.logits.shape == (sz, 4)
    finally:
        rt.close()


def test_runtime_stamps_arrival_at_the_due_time(served):
    """A request submitted late counts the lateness: arrival is stamped at
    the due time, so it shows in queueing and latency; ``replay`` hands
    each request's due time over."""
    rt = _runtime(served)
    try:
        _calibrate(rt)
        late_s = 0.25
        due = time.monotonic() - late_s
        h = rt.submit(Request(rid=0, seeds=np.arange(2, dtype=np.int32),
                              arrival_s=0.0, slo_ms=30_000.0), due)
        resp = h.wait(timeout=10.0)
        assert resp.status == OK
        assert resp.queue_ms >= late_s * 1e3
        assert resp.latency_ms >= resp.queue_ms
    finally:
        rt.close()
    seen = []
    load = OpenLoopLoad(160, rate_rps=2000.0, num_requests=4, seed=3)
    t0 = time.monotonic()
    assert load.replay(lambda r, d: seen.append((r, d))) == 4
    for r, d in seen:
        assert d == pytest.approx(t0 + r.arrival_s, abs=0.05)


def test_runtime_rejects_unmeetable_deadline(served):
    rt = _runtime(served)
    try:
        _calibrate(rt)
        h = rt.submit(Request(rid=0, seeds=np.arange(2, dtype=np.int32),
                              arrival_s=0.0, slo_ms=1e-6))
        resp = h.wait(timeout=10.0)
        assert resp is not None and resp.status == REJECTED_DEADLINE
        assert resp.logits is None
    finally:
        rt.close()
    assert rt.stats()["deadline_misses"] == 1


def test_runtime_oversized_request_raises(served):
    rt = _runtime(served)
    try:
        with pytest.raises(ValueError, match="exceed the top"):
            rt.submit(Request(rid=0, seeds=np.arange(64, dtype=np.int32),
                              arrival_s=0.0, slo_ms=1000.0))
    finally:
        rt.close()


def test_runtime_close_is_graceful_and_leaves_no_threads(served):
    """close() drains: queued requests terminate (served or rejected with
    REJECTED_SHUTDOWN), every handle resolves, and no worker thread
    survives — including the loader's prefetch thread."""
    rt = _runtime(served)
    try:
        _calibrate(rt)
        rt.start()
        handles = [
            rt.submit(Request(rid=i, seeds=np.arange(1, dtype=np.int32),
                              arrival_s=0.0, slo_ms=30_000.0))
            for i in range(6)]
    finally:
        rt.close()
    for h in handles:
        resp = h.wait(timeout=5.0)
        assert resp is not None
        assert resp.status in (OK, LATE, REJECTED_SHUTDOWN)
    assert all(not t.is_alive() for t in rt.worker_threads() if t)
    # post-close submissions are rejected, not queued
    h = rt.submit(Request(rid=99, seeds=np.arange(1, dtype=np.int32),
                          arrival_s=0.0, slo_ms=1000.0))
    assert h.wait(timeout=1.0).status == REJECTED_SHUTDOWN
    rt.close()   # idempotent


def test_runtime_close_without_start(served):
    rt = _runtime(served)
    rt.close()
    assert all(not t.is_alive() for t in rt.worker_threads() if t)


# ---------------------------------------------------------------------------
# multi-model tenancy
# ---------------------------------------------------------------------------
def test_tenancy_routes_by_model_and_never_cross_retraces(served):
    """Two tenants share the process; traffic routed by Request.model.
    Serving one tenant must never retrace the other (isolation comes from
    per-plan compile-cache keys): after each tenant's own calibration,
    interleaved two-tenant traffic leaves both at zero retraces."""
    graph, engine_a, params_a, store_a = served
    engine_b = hector.compile("rgcn", graph, layers=1, dim=8, hidden=8,
                              classes=4, sample=2, tile=8, node_block=8,
                              bucket=True, seed=0)
    params_b = engine_b.init(jax.random.key(1))
    feats = np.random.default_rng(2).normal(
        size=(graph.num_nodes, 8)).astype(np.float32)
    store_b = engine_b.make_feature_store(feats)

    mt = MultiTenantRuntime()
    mt.add(ServingRuntime(engine_a, params_a, store_a, name="a",
                          rungs=ladder(4, "fine"), max_wait_ms=2.0))
    mt.add(ServingRuntime(engine_b, params_b, store_b, name="b",
                          rungs=ladder(4, "fine"), max_wait_ms=2.0))
    try:
        mt.calibrate(batches_per_rung=1, validate=False, iters=1,
                     probe_batches=4, warm_rounds=2)
        load = OpenLoopLoad(graph.num_nodes, rate_rps=400.0,
                            num_requests=16, size_choices=(1, 2),
                            slo_ms=30_000.0, models=("a", "b"), seed=5)
        handles = [mt.submit(r) for r in load.requests()]
        mt.drain(timeout=60.0)
        assert all(h.wait(timeout=10.0).status == OK for h in handles)
        s = mt.stats()
        assert s["tenants"]["a"]["requests"] == 8
        assert s["tenants"]["b"]["requests"] == 8
        assert s["tenants"]["a"]["retraces_after_warmup"] == 0
        assert s["tenants"]["b"]["retraces_after_warmup"] == 0
        assert s["retraces_after_warmup"] == 0
    finally:
        mt.close()
    assert all(not t.is_alive() for t in mt.worker_threads() if t)


def test_tenancy_routing_errors():
    mt = MultiTenantRuntime()
    with pytest.raises(RuntimeError):
        mt.start()
    req = Request(rid=0, seeds=np.arange(1, dtype=np.int32),
                  arrival_s=0.0, slo_ms=10.0, model="ghost")
    with pytest.raises(KeyError):
        mt.submit(req)
