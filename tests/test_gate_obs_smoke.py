"""Observability end to end: serve and train with tracing on, then check
every exported artifact.

Four runs export a Chrome trace and a metrics snapshot each under the
test's own temporary directory: host-sampled serving, sampled training,
device-sampled serving and the online (open-loop) runtime. Each trace and
snapshot conforms to its schema (``repro.obs.schema``), each run's phase
spans are present with nonzero duration, and the counters and histograms
the drivers report from are populated, not merely schema-valid.
"""
import json

import pytest

from repro.launch.serve_rgnn import serve, serve_online
from repro.launch.train_rgnn import train
from repro.obs import schema
from repro.obs.registry import (snapshot_counter_total, snapshot_histogram,
                                snapshot_histograms)

SERVE_CONFIG = dict(
    model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], batch_size=8, num_batches=4, tile=8,
    node_block=8, seed=0,
)
TRAIN_CONFIG = dict(
    model="rgat", dataset="synthetic", scale=0.05, layers=2, dim=8,
    hidden=8, classes=4, fanouts=[3, 3], batch_size=16, epochs=1, tile=8,
    node_block=8, eval_every_epochs=0, seed=0,
)
ONLINE_CONFIG = dict(
    model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], tile=8, node_block=8, seed=0,
    max_batch=8, rate_rps=300.0, num_requests=12, size_choices=(1, 2, 4),
    slo_ms=5000.0,
)
# the phase spans each run must record; with the device sampler the jit
# pipeline's spans replace the host sample/layout phases, and the online
# runtime adds its worker-thread spans (one "coalesce" per admission
# decision, one "execute_async" per executed batch)
PHASES = {
    "serve": ("sample", "layout", "execute"),
    "train": ("sample", "layout", "train_step"),
    "serve_device": ("sample_device", "layout_device", "execute"),
    "serve_online": ("coalesce", "execute_async", "sample", "layout",
                     "execute"),
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """kind -> (stats, trace document, metrics document)."""
    out = tmp_path_factory.mktemp("obs")
    drivers = {
        "serve": lambda **kw: serve(**SERVE_CONFIG, **kw),
        "train": lambda **kw: train(**TRAIN_CONFIG, **kw),
        "serve_device": lambda **kw: serve(sampler="device", **SERVE_CONFIG,
                                           **kw),
        "serve_online": lambda **kw: serve_online(**ONLINE_CONFIG, **kw),
    }
    result = {}
    for kind, run in drivers.items():
        trace = out / f"{kind}_trace.json"
        metrics = out / f"{kind}_metrics.json"
        stats = run(trace_out=str(trace), metrics_out=str(metrics),
                    log=lambda *a, **k: None)
        result[kind] = (stats, json.loads(trace.read_text()),
                        json.loads(metrics.read_text()))
    return result


@pytest.mark.parametrize("kind", list(PHASES))
def test_trace_schema_valid(runs, kind):
    assert schema.validate_trace(runs[kind][1]) == []


@pytest.mark.parametrize("kind", list(PHASES))
def test_phase_spans_present_and_nonzero(runs, kind):
    assert schema.require_phases(runs[kind][1], PHASES[kind]) == []


@pytest.mark.parametrize("kind", list(PHASES))
def test_metrics_schema_valid(runs, kind):
    assert schema.validate_metrics(runs[kind][2]) == []


def test_device_serve_never_falls_back_to_host(runs):
    n = runs["serve_device"][0]["host_builds"]
    assert n == 0, (
        f"serve[device]: {n} batches fell back to the host sampling "
        f"pipeline")


def _tenants(runs):
    return snapshot_histograms(runs["serve_online"][0]["metrics"],
                               "serve_request_ms")


def test_online_request_histogram_present(runs):
    assert _tenants(runs), (
        "serve[online] metrics: no serve_request_ms histogram (tenant-"
        "labeled request latency missing)")


def test_online_request_histogram_tenant_labeled(runs):
    labels = [t["labels"] for t in _tenants(runs)]
    assert {lb.get("model") for lb in labels} == {ONLINE_CONFIG["model"]}, (
        f"serve[online] metrics: serve_request_ms labels {labels} missing "
        f"the tenant label")


def test_online_request_histogram_counts_completed(runs):
    stats = runs["serve_online"][0]
    done = sum(n for s, n in stats["by_status"].items()
               if s in ("ok", "late"))
    recorded = sum(t["summary"]["count"] for t in _tenants(runs))
    assert recorded == done, (
        f"serve[online] metrics: serve_request_ms recorded {recorded} of "
        f"{done} completed requests")


@pytest.mark.parametrize("counter", ["serve_requests", "serve_batches"])
def test_online_counter_populated(runs, counter):
    m = runs["serve_online"][0]["metrics"]
    assert snapshot_counter_total(m, counter) > 0, (
        f"serve[online] metrics: {counter} counter empty")


def test_serve_executor_traces_populated(runs):
    m = runs["serve"][0]["metrics"]
    assert snapshot_counter_total(m, "executor_traces") > 0, (
        "serve metrics: executor_traces counter empty")


def test_serve_batch_histogram_counts_batches(runs):
    stats = runs["serve"][0]
    h = snapshot_histogram(stats["metrics"], "serve_batch_ms")
    assert h and h["count"] == stats["batches"], (
        f"serve metrics: serve_batch_ms recorded {h['count'] if h else 0} "
        f"of {stats['batches']} batches")


def test_train_step_histogram_counts_steps(runs):
    stats = runs["train"][0]
    h = snapshot_histogram(stats["metrics"], "train_step_ms")
    assert h and h["count"] == stats["steps"], (
        f"train metrics: train_step_ms recorded {h['count'] if h else 0} "
        f"of {stats['steps']} steps")
