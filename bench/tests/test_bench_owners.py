"""The readers of device time by owner (forward, backward, the backward's
scatter-adds, unattributed): a hand-made optimized program's table
joined with a hand-made trace."""
import collections
import sys

import pytest

from bench import device_owners, trace_reduce
from bench.harness import metric_reader
from repro.obs import device_ops

READERS = ("forward_ms.train_full", "backward_ms.train_full",
           "grad_scatter_ms.train_full", "unattributed_ms.train_full")

_META = 'metadata={op_name="jit(hector_train_step)/%s"}'
HLO = "\n".join([
    "HloModule jit_hector_train_step, entry_computation_layout={()->f32[8]}",
    "",
    "%fused_computation (param_0: f32[8]) -> f32[8] {",
    "  %param_0 = f32[8]{0} parameter(0)",
    "  ROOT %scatter.1 = f32[8]{0} scatter(%param_0), " + _META
    % "transpose(jvp(l0.traversal.h_out))/scatter-add",
    "}",
    "",
    "%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {",
    "  %param_0.1 = f32[8]{0} parameter(0)",
    "  %exp.1 = f32[8]{0} exponential(%param_0.1), " + _META
    % "jvp(l1.traversal.h_out)/exp",
    "  %neg.1 = f32[8]{0} negate(%exp.1), " + _META
    % "jvp(l1.traversal.h_out)/neg",
    "  ROOT %bitcast.1 = f32[8]{0} bitcast(%neg.1)",
    "}",
    "",
    "ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {",
    "  %Arg_0.1 = f32[8]{0} parameter(0)",
    "  %fusion.1 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, "
    "calls=%fused_computation",
    "  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, "
    "calls=%fused_computation.1",
    "  %segment_mm_padded.3 = f32[8]{0} custom-call(%fusion.2), "
    'custom_call_target="tpu_custom_call", ' + _META
    % "jvp(l0.gemm.hs)/jit(segment_mm_padded)/segment_mm_padded/pallas_call",
    "  %dot.4 = f32[8]{0} dot(%segment_mm_padded.3), " + _META
    % "transpose(jvp(loss))/dot_general",
    "  %add.5 = f32[8]{0} add(%dot.4, %dot.4), " + _META % "optimizer/add",
    "  ROOT %copy.6 = f32[8]{0} copy(%add.5)",
    "}",
])

# seconds over 2 steps, by the trace's op names
OPS = {"%fusion.1 = f32[8]{0}": 2.0,            # backward scatter-add
       "%fusion.2 = f32[8]{0}": 0.5,            # forward (fused majority)
       "%segment_mm_padded.3 = f32[8]{0}": 1.0,   # forward
       "%dot.4 = f32[8]{0}": 0.5,               # backward
       "%add.5 = f32[8]{0}": 0.25,              # optimizer
       "%copy.6 = f32[8]{0}": 0.25}             # no owner
STEPS = 2


class _Compiled:
    def as_text(self):
        return HLO


@pytest.fixture
def registry(monkeypatch):
    monkeypatch.setattr(device_ops, "_TABLES", collections.OrderedDict())
    return device_ops


def _data():
    busy = sum(OPS.values())
    return {"trace": trace_reduce.DeviceTrace(busy, busy, 1, dict(OPS), []),
            "steps": STEPS}


def test_table_of_a_hand_made_program(registry):
    assert registry.record(_Compiled()) == device_owners.STEP_MODULE
    own = lambda n: registry.owner(n, device_owners.STEP_MODULE)  # noqa
    assert own("%fusion.1 = f32[8]{0}") == device_ops.Owner(
        "l0.traversal.h_out", "backward", "scatter-add")
    # a fusion whose root carries no metadata: its fused instructions' owner
    assert own("fusion.2") == device_ops.Owner(
        "l1.traversal.h_out", "forward", "exp")
    assert own("segment_mm_padded.3").owner == "l0.gemm.hs"
    assert own("dot.4") == device_ops.Owner("loss", "backward",
                                            "dot_general")
    assert own("add.5").owner == "optimizer"
    assert own("copy.6") is None and own("param_0") is None
    assert registry.owner("dot.4", "jit_other") is None


@pytest.mark.parametrize("name,want", [
    ("forward_ms.train_full", (0.5 + 1.0) / STEPS * 1e3),
    ("backward_ms.train_full", (2.0 + 0.5) / STEPS * 1e3),
    ("grad_scatter_ms.train_full", 2.0 / STEPS * 1e3),
    ("unattributed_ms.train_full", 0.25 / STEPS * 1e3),
])
def test_reader_values(registry, name, want):
    registry.record(_Compiled())
    assert metric_reader(name)(_data()) == pytest.approx(want)


def test_owners_add_up_to_busy_time(registry):
    registry.record(_Compiled())
    data = _data()
    parts = [metric_reader(n)(data) for n in READERS if "scatter" not in n]
    optimizer = device_owners.ms_per_step(
        data, lambda o: o is not None and o.owner == "optimizer")
    assert sum(parts) + optimizer == pytest.approx(
        data["trace"].busy_s / STEPS * 1e3)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_table_reads_nothing(registry, name, monkeypatch):
    assert metric_reader(name)(_data()) is None
    # a program that has no table module at all
    import repro.obs
    registry.record(_Compiled())
    monkeypatch.delattr(repro.obs, "device_ops")
    monkeypatch.setitem(sys.modules, "repro.obs.device_ops", None)
    assert metric_reader(name)(_data()) is None
