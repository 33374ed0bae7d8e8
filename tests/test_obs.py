"""Unified observability layer: metrics registry correctness (counters,
streaming histograms, percentile edge cases, scope merging), span tracer
nesting + Chrome-trace schema, the per-op plan profiler's telescoping-sum
invariant, and the disabled-mode zero-recording / zero-retrace contract."""
import json
import math
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import hector
from repro import obs
from repro.core.graph import synthetic_heterograph
from repro.obs import schema
from repro.obs.registry import (MetricsRegistry, NULL_REGISTRY,
                                snapshot_counter_total, snapshot_histogram,
                                snapshot_value)
from repro.obs.tracing import NULL_SPAN, SpanTracer
from repro.optim import AdamW
from repro.sampling import build_minibatch


# ---------------------------------------------------------------------------
# registry: counters / gauges / labels
# ---------------------------------------------------------------------------
def test_counter_identity_by_name_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("hits", cache="block")
    b = reg.counter("hits", cache="block")
    c = reg.counter("hits", cache="layout")
    assert a is b and a is not c
    a.inc()
    b.inc(4)
    assert reg.value("hits", cache="block") == 5
    assert reg.value("hits", cache="layout") == 0
    assert reg.value("hits", cache="nope") is None
    assert reg.counter_total("hits") == 5


def test_gauge_last_write_wins():
    reg = MetricsRegistry()
    reg.gauge("depth").set(3)
    reg.gauge("depth").set(7)
    assert reg.value("depth") == 7.0


# ---------------------------------------------------------------------------
# registry: histogram percentiles, edge cases, reservoir
# ---------------------------------------------------------------------------
def test_histogram_empty_and_single_sample():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    s = h.summary()
    assert s["count"] == 0
    assert math.isnan(s["p50"]) and math.isnan(s["min"])
    h.observe(4.5)
    s = h.summary()
    assert s["count"] == 1
    # a single sample IS every percentile
    assert s["p50"] == s["p99"] == s["min"] == s["max"] == 4.5


def test_histogram_linear_interpolation_matches_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    vals = [5.0, 1.0, 9.0, 3.0, 7.0]
    for v in vals:
        h.observe(v)
    for q in (50, 90, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q))
    s = h.summary()
    assert s["mean"] == pytest.approx(5.0)
    assert s["min"] == 1.0 and s["max"] == 9.0 and s["sum"] == 25.0


def test_histogram_reservoir_exact_aggregates_and_determinism():
    def fill():
        reg = MetricsRegistry()
        h = reg.histogram("lat", max_samples=128)
        for i in range(5000):
            h.observe(float(i))
        return h

    a, b = fill(), fill()
    # count/sum/min/max stay exact past the reservoir bound
    assert a.count == 5000 and a.min == 0.0 and a.max == 4999.0
    assert a.total == pytest.approx(sum(range(5000)))
    # the LCG reservoir is deterministic: identical streams -> identical
    # samples -> identical percentiles
    assert a.summary() == b.summary()
    # and the sampled p50 is in the right neighborhood
    assert 1500 < a.percentile(50) < 3500


def test_histogram_absorb_merges_distributions():
    a, b = MetricsRegistry(), MetricsRegistry()
    for v in (1.0, 2.0):
        a.histogram("lat").observe(v)
    for v in (3.0, 4.0):
        b.histogram("lat").observe(v)
    a.absorb(b)
    s = a.histogram_summary("lat")
    assert s["count"] == 4 and s["min"] == 1.0 and s["max"] == 4.0
    assert s["sum"] == 10.0


def test_snapshot_readers_round_trip():
    reg = MetricsRegistry()
    reg.counter("traces", executor="BlockExecutor").inc(3)
    reg.counter("traces", executor="StackExecutor").inc(2)
    reg.gauge("tile").set(16)
    reg.histogram("lat").observe(2.0)
    snap = json.loads(json.dumps(reg.snapshot()))  # through-JSON fidelity
    assert snap["schema_version"] == obs.SCHEMA_VERSION
    assert snapshot_value(snap, "traces", executor="BlockExecutor") == 3
    assert snapshot_counter_total(snap, "traces") == 5
    assert snapshot_value(snap, "tile") == 16.0
    assert snapshot_histogram(snap, "lat")["count"] == 1
    assert snapshot_value(snap, "absent") is None
    assert schema.validate_metrics(snap) == []


# ---------------------------------------------------------------------------
# scopes: activation, nesting, absorb-on-exit, disabled mode
# ---------------------------------------------------------------------------
def test_metrics_null_outside_scope_and_live_inside():
    assert obs.metrics() is NULL_REGISTRY
    assert obs.span("x") is NULL_SPAN
    assert not obs.enabled()
    with obs.scope(metrics=True) as sc:
        assert obs.metrics() is sc.registry
        obs.metrics().counter("c").inc()
        assert sc.registry.value("c") == 1
    assert obs.metrics() is NULL_REGISTRY
    # nothing leaked into the null registry
    assert NULL_REGISTRY.counter("c").value == 0


def test_nested_scope_folds_into_parent():
    with obs.scope(metrics=True, tracing=True) as outer:
        obs.metrics().counter("c").inc()
        with obs.scope(metrics=True, tracing=True) as inner:
            obs.metrics().counter("c").inc(10)
            with obs.span("phase"):
                pass
            assert inner.registry.value("c") == 10
        # child absorbed: counters add, spans land on the parent tracer
        assert outer.registry.value("c") == 11
        assert len(outer.tracer.events("phase")) == 1


def test_disabled_forces_null_even_inside_scope():
    with obs.scope(metrics=True, tracing=True):
        with obs.disabled():
            assert obs.metrics() is NULL_REGISTRY
            assert obs.span("x") is NULL_SPAN
            assert not obs.enabled()
        assert obs.metrics() is not NULL_REGISTRY


# ---------------------------------------------------------------------------
# tracer: nesting, threads, Chrome-trace schema
# ---------------------------------------------------------------------------
def test_span_nesting_depth_and_containment():
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    outer, = tr.events("outer")
    inner, = tr.events("inner")
    assert outer["depth"] == 0 and inner["depth"] == 1
    # the inner interval nests inside the outer one
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_chrome_trace_schema_and_thread_tracks():
    tr = SpanTracer()
    with tr.span("execute", step=0):
        pass

    def worker():
        with tr.span("sample"):
            pass
    t = threading.Thread(target=worker, name="prefetch")
    t.start()
    t.join()

    doc = json.loads(json.dumps(tr.chrome_trace()))
    assert schema.validate_trace(doc) == []
    assert schema.require_phases(doc, ["execute", "sample"]) == []
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    spans = [e for e in evs if e["ph"] == "X"]
    # two threads -> two named tracks, spans on distinct tids
    assert {m["args"]["name"] for m in meta} >= {"prefetch"}
    tids = {e["tid"] for e in spans}
    assert len(tids) == 2
    for e in spans:
        assert e["pid"] == 0 and e["dur"] >= 0 and e["cat"] == "phase"
    # a missing phase is reported, not silently passed
    assert schema.require_phases(doc, ["backward"]) != []


def test_tracer_absorb_rebases_and_merges_tracks():
    parent, child = SpanTracer(), SpanTracer()
    with parent.span("a"):
        pass
    with child.span("b"):
        pass
    parent.absorb(child)
    assert parent.num_events == 2
    names = {e["name"] for e in parent.events()}
    assert names == {"a", "b"}
    # both main-thread spans share one re-mapped track
    assert len({e["tid"] for e in parent.events()}) == 1


def test_tracer_bounded_drops_not_grows():
    tr = SpanTracer(max_events=2)
    for _ in range(5):
        with tr.span("x"):
            pass
    assert tr.num_events == 2 and tr.dropped == 3


def test_spans_on_the_profiler_clock(tmp_path):
    """A span is a ``TraceAnnotation``: a profiler capture holds it on the
    host plane with its arguments, with no obs scope open, and a tracing
    scope keeps it as well. With no profiler it is the no-op span again."""
    from jax.profiler import ProfileData
    x = jnp.ones(4)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("x", step=3):
            (x * 2).block_until_ready()
        with obs.scope(metrics=False, tracing=True) as sc:
            with obs.span("y", step=4):
                pass
    path, = tmp_path.glob("**/*.xplane.pb")
    host = {ev.name: dict(ev.stats)
            for pl in ProfileData.from_file(str(path)).planes
            if pl.name.startswith("/host") for ln in pl.lines
            for ev in ln.events}
    assert host["x"] == {"step": 3} and host["y"] == {"step": 4}
    assert len(sc.tracer.events("y")) == 1
    assert obs.span("x", step=3) is NULL_SPAN


# ---------------------------------------------------------------------------
# profiler: telescoping-sum invariant on a real compiled model
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def profiled():
    graph = synthetic_heterograph(num_nodes=120, num_edges=900,
                                  num_ntypes=4, num_etypes=7, seed=0)
    rng = np.random.default_rng(1)
    feats = jnp.asarray(rng.normal(size=(graph.num_nodes, 8)), jnp.float32)
    eng = hector.compile("rgat", graph, layers=2, dim=8, hidden=8,
                         classes=4, sample=[3, 3], tile=8, node_block=8,
                         log=None)
    params = eng.init(0)
    seq = eng.sampler.sample(np.arange(8, dtype=np.int32), batch_index=0,
                             epoch=0)
    mb = build_minibatch(seq, step=0, tile=8, node_block=8, bucket=True)
    return eng, params, mb, feats


def test_profile_minibatch_structure_and_coverage(profiled):
    eng, params, mb, feats = profiled
    p = eng.profile(params, mb, feats, warmup=1, iters=3)
    n_plan_ops = sum(len(pl.ops) for pl in eng.plans)
    # every op instance appears, plus one glue row per hop
    assert len(p.ops) == n_plan_ops + len(eng.plans)
    assert {o.hop for o in p.ops} == {0, 1}
    assert {o.category for o in p.ops} <= {"gemm", "traversal", "wprod",
                                           "glue"}
    assert all(o.seconds >= 0 for o in p.ops)
    assert p.total_seconds > 0
    # prefix differences telescope: the attributed sum must land near the
    # whole-plan time (generous band: CI boxes are noisy, and the invariant
    # being tested is structural consistency, not machine quietness)
    assert 0.5 < p.coverage < 1.6, p.table()
    # category rollup and JSON export agree with the rows
    assert sum(p.by_category().values()) == pytest.approx(p.sum_op_seconds)
    doc = json.loads(json.dumps(p.to_json()))
    assert doc["total_us"] > 0 and len(doc["ops"]) == len(p.ops)
    assert p.table().count("\n") >= len(p.ops)


def test_profile_train_step_phases(profiled):
    from repro.obs.profile import profile_train_step
    eng, params, mb, feats = profiled
    opt = AdamW(learning_rate=1e-3)
    state = opt.init(params)
    labels = np.zeros(8, dtype=np.int32)
    ph = profile_train_step(
        eng.plans, opt, state, mb, labels,
        {"feature": jnp.asarray(feats)[mb.input_ids]},
        backend=eng.cfg.backend, activation=eng.cfg.activation,
        decisions=eng.decisions, warmup=1, iters=3)
    assert set(ph) == {"forward", "backward", "optimizer", "total"}
    assert ph["forward"] > 0 and ph["total"] > 0
    assert all(v >= 0 for v in ph.values())
    # the fused step can't be faster than its forward pass
    assert ph["total"] >= ph["forward"] * 0.5


def test_isotonic_fit_is_monotone_and_mass_preserving():
    from repro.obs.profile import _isotonic
    xs = [1.0, 3.0, 2.0, 2.0, 5.0, 4.0]
    fit = _isotonic(xs)
    assert all(b >= a for a, b in zip(fit, fit[1:]))
    assert sum(fit) == pytest.approx(sum(xs))
    # already-monotone input passes through untouched
    assert _isotonic([1.0, 2.0, 3.0]) == [1.0, 2.0, 3.0]


# ---------------------------------------------------------------------------
# disabled mode: zero recording, no trace-behavior side effects
# ---------------------------------------------------------------------------
def test_serve_disabled_records_nothing_and_keeps_zero_retraces():
    from repro.launch.serve_rgnn import serve
    kwargs = dict(model="rgat", dataset="aifb", scale=0.05, layers=2,
                  dim=8, hidden=8, classes=4, fanouts=[3, 3], batch_size=8,
                  num_batches=6, tile=8, node_block=8, repeat_after=2,
                  cache_blocks=8, cache_layouts=32,
                  log=lambda *a, **k: None)
    off = serve(obs_mode="off", **kwargs)
    # no registry snapshot, nothing recorded anywhere
    assert "metrics" not in off
    assert off["retraces_after_warmup"] == 0
    assert NULL_REGISTRY.counter("executor_traces").value == 0

    on = serve(obs_mode="on", **kwargs)
    assert "metrics" in on
    # enabling observability must not change compile/trace behavior
    assert on["retraces_after_warmup"] == 0
    assert on["executor_traces"] == off["executor_traces"]
    assert snapshot_counter_total(on["metrics"], "executor_traces") \
        == on["executor_traces"]
    # registry-sourced latency percentiles are present and sane
    hs = snapshot_histogram(on["metrics"], "serve_batch_ms")
    assert hs["count"] == on["batches"]
    assert hs["p50"] <= hs["p99"]
