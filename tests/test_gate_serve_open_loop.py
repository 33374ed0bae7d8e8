"""Open-loop online serving: the async request pipeline under seeded
Poisson traffic.

A deterministic arrival schedule goes through the ``ServingRuntime``
(deadline-aware coalescing over the calibrated batch-size ladder). The
calibration pins the compiled shape set before traffic starts, so the
executor never retraces during traffic, and the drain answers every
submitted request. Latency and SLO attainment are CPU wall-clock figures
and are not asserted here.
"""
import pytest

from repro.launch.serve_rgnn import serve_online

# one small bucketed config: 2-hop rgat over the reduced AIFB graph
CONFIG = dict(
    model="rgat", dataset="aifb", scale=0.05, layers=2, dim=8, hidden=8,
    classes=4, fanouts=[3, 3], tile=8, node_block=8, seed=0,
    max_batch=8, max_wait_ms=3.0,
)
LOAD = dict(rate_rps=200.0, num_requests=32, process="poisson",
            size_choices=(1, 2, 4), slo_ms=2000.0)


@pytest.fixture(scope="module")
def stats():
    return serve_online(backend="xla", ladder_kind="fine",
                        log=lambda *a, **k: None, **CONFIG, **LOAD)


def test_zero_retraces_during_traffic(stats):
    assert stats["retraces_after_warmup"] == 0, (
        f"executor retraced {stats['retraces_after_warmup']}x during "
        f"traffic (expected 0: calibration must pin the shape set)")


def test_every_request_answered(stats):
    assert stats["requests"] == LOAD["num_requests"], (
        f"{stats['requests']} terminal responses for "
        f"{LOAD['num_requests']} submitted requests (drain leaked)")
