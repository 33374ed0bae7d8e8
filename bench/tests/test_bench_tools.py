"""The rules of the tools that set a cell's numbers: the sweep's knee, and
the verdict ``bench/calibrate.py`` gives a reading under a cell's limits."""
import pytest

import bench_tiny as tiny
from bench import calibrate, sweep


def _row(rate, growth, missing=0):
    return {"rate_rps": rate, "growth": growth, "missing": missing}


def test_knee_is_the_last_steady_rate_before_the_first_unsteady_one():
    rows = [_row(25, 1.0), _row(100, 1.02), _row(110, 0.95), _row(120, 1.4),
            _row(130, 1.1)]
    # 130 reads steady again, but the queue already grew at 120
    assert sweep.knee(rows) == 110
    assert sweep.knee(rows[:3]) == 110
    assert sweep.knee([_row(25, 1.0), _row(100, 1.0, missing=1)]) == 25
    with pytest.raises(RuntimeError):
        sweep.knee([_row(25, 2.0)])


def test_calibrate_verdict_uses_the_cells_limits(tmp_path):
    c = tiny.cell("rgat-am.train_full", tmp_path)
    names = ("loss_gap", "grad_gap", "update_gap")
    under = {k: 0.5 * c.limit(k) for k in names}
    assert calibrate.verdict(c, under, names) == {"correct": True,
                                                  "failed": []}
    over = dict(under, grad_gap=2 * c.limit("grad_gap"))
    assert calibrate.verdict(c, over, names) == {"correct": False,
                                                 "failed": ["grad_gap"]}
    nan = dict(under, loss_gap=float("nan"))
    assert not calibrate.verdict(c, nan, names)["correct"]
