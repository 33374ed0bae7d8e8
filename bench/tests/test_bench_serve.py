"""The open-loop serving cell end to end on the CPU at a tiny size
(interpret-mode kernels), through the runner the chip runs; and the check
that decides ``correct`` seen to fail with the timed path broken
underneath, and with the control in the program's place."""
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
from bench.runners import serve_open_loop


def test_serve_cell_is_correct(tmp_path):
    c = tiny.cell("rgcn-mag.serve_poisson", tmp_path)
    out = tiny.run(c)
    assert tiny.correct(out), out.checks
    assert out.attempted == round(c.traffic["rate_rps"] * c.seconds)
    assert out.metrics["request_p95_ms"] > 0
    assert out.layer["queue_ms"]



# --- faults in the timed path: each must make ``correct`` false ----------
def _logit_altered(monkeypatch):
    from repro.train.engine import RGNNEngine
    orig = RGNNEngine.forward_minibatch

    def fwd(self, *args, **kw):
        out = orig(self, *args, **kw)
        return out.at[0, 0].add(0.5 * jnp.max(jnp.abs(out)) + 1.0)
    monkeypatch.setattr(RGNNEngine, "forward_minibatch", fwd)


def _edge_dropped(monkeypatch):
    from repro.sampling.sampler import FanoutSampler
    orig = FanoutSampler._sample_in_edges

    def drop(self, *args):
        src, dst, et = orig(self, *args)
        return src[1:], dst[1:], et[1:]
    monkeypatch.setattr(FanoutSampler, "_sample_in_edges", drop)


@pytest.mark.parametrize("fault", [_logit_altered, _edge_dropped])
def test_serve_fault_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = tiny.run(tiny.cell("rgcn-mag.serve_poisson", tmp_path))
    assert not tiny.correct(out), out.checks


def test_schedule_is_fixed_work_in_seeded_order():
    traffic = {"rate_rps": 50, "process": "poisson", "sizes": [1, 2, 4, 8],
               "popularity": "uniform"}
    a = serve_open_loop.schedule(traffic, 2.0, 1000, seed=1)
    b = serve_open_loop.schedule(traffic, 2.0, 1000, seed=2)
    assert len(a) == len(b) == 100
    sizes = lambda s: sorted(r["seeds"].size for r in s)  # noqa: E731
    assert sizes(a) == sizes(b)
    arrivals = [r["arrival_s"] for r in a]
    assert arrivals == sorted(arrivals) and 0 <= arrivals[0]
    assert arrivals[-1] < 2.0
    again = serve_open_loop.schedule(traffic, 2.0, 1000, seed=1)
    assert all(np.array_equal(x["seeds"], y["seeds"])
               for x, y in zip(a, again))


@pytest.mark.parametrize("precision", ["high", "bfloat16"])
def test_serve_control_is_not_correct(tmp_path, precision):
    import jax
    c = tiny.cell("rgcn-mag.serve_poisson", tmp_path)
    jax.config.update("jax_default_matmul_precision", "highest")
    counter = tiny.harness.CompileCounter()
    try:
        m = serve_open_loop.measure(c, jax.devices(), counter)
    finally:
        counter.close()
    ref = serve_open_loop.check_batches(c, m["arrays"], m["checked"],
                                        m["recorder"])
    ctl = serve_open_loop.check_batches(c, m["arrays"], m["checked"],
                                        m["recorder"], precision=precision)
    assert ref["rows"] > 0 and ctl["rows"] == ref["rows"]
    gap = serve_open_loop.logits_gap(ctl["ref"], ref["ref"])
    assert gap > c.limit("logits_gap"), gap
