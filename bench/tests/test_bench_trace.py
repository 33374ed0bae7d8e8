"""The trace reduction: busy and idle share, per-op device time and the
naming of idle gaps, on hand-made planes and on a trace recorded on a TPU
v5e (a few full-graph RGAT training steps at a tiny size)."""
import pathlib

import pytest

from bench import trace_reduce

MS = 1e6   # nanoseconds


def _planes():
    host = ("/host:CPU", [("python", [
        ("bench.window", 10 * MS, 100 * MS),
        ("bench.sample", 20 * MS, 15 * MS),
        ("bench.execute", 50 * MS, 30 * MS),
        ("not_ours", 0, 200 * MS),
    ])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("_mm_kernel", 0, 15 * MS),        # half before window
                     ("fusion.1", 40 * MS, 10 * MS),
                     ("_stats_kernel", 45 * MS, 10 * MS),   # overlaps
                     ("_mm_kernel", 60 * MS, 20 * MS)]),
        ("XLA Modules", [("jit_step", 0, 200 * MS)]),
    ])
    return [host, dev]


def test_reduce_hand_made_planes():
    dt = trace_reduce.reduce_planes(_planes())
    assert dt.window_s == pytest.approx(0.100)
    # busy: [10,15) + [40,55) + [60,80) = 40 ms of the 100 ms window
    assert dt.busy_s == pytest.approx(0.040)
    assert dt.idle_share == pytest.approx(0.6)
    assert dt.op_seconds["_mm_kernel"] == pytest.approx(0.025)
    assert dt.family_seconds(["_mm_kernel", "_stats_kernel"]) == \
        pytest.approx(0.035)
    gaps = dict(dt.top_gaps())
    # gaps [15,40) sample, [55,60) execute, [80,110) execute 0 / none
    assert gaps["bench.sample"] == pytest.approx(0.025)
    assert gaps["bench.execute"] == pytest.approx(0.005)
    assert gaps["no_host_span"] == pytest.approx(0.030)
    assert sum(gaps.values()) == pytest.approx(0.060)
    assert dt.top_ops(1) == [["_mm_kernel", pytest.approx(0.025)]]


def test_reduce_needs_the_window_and_a_device():
    host, dev = _planes()
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([dev])
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([host])


TPU_TRACE = pathlib.Path(__file__).with_name("data") / "tpu_v5e_train.xplane.pb"


def test_reduce_recorded_tpu_trace():
    import json
    dt = trace_reduce.load(str(TPU_TRACE))
    assert dt.num_devices == 1
    assert 0 < dt.busy_s <= dt.window_s
    names = json.loads((pathlib.Path(__file__).parents[1] / "metrics"
                        / "kernel_names.json").read_text())
    assert dt.family_seconds(names["segment_mm"]) > 0
    assert dt.family_seconds(names["traversal"]) > 0
    assert sum(s for _, s in dt.gaps) == pytest.approx(
        dt.window_s - dt.busy_s, rel=1e-6)
    # ops are keyed by instruction name and type, not their whole HLO text
    assert all(" = " in k and "(" not in k.split(" = ")[0]
               for k in dt.op_seconds)


def test_op_name_keeps_the_instruction_and_its_type():
    text = ("%fusion.39 = f32[3000,64]{1,0:T(8,128)S(1)} fusion(f32[3000,64]"
            " %add.8, f32[13824,64] %transpose_jvp_jit_segment_mm_padded___.8"
            "), kind=kCustom")
    name = trace_reduce.op_name(text)
    assert name == "%fusion.39 = f32[3000,64]{1,0:T(8,128)S(1)}"
    dt = trace_reduce.DeviceTrace(1.0, 0.5, 1, {name: 0.5}, [])
    assert dt.family_seconds(["segment_mm_padded"]) == 0


def test_result_line_of_a_traced_run(tmp_path):
    """A ``--trace 1`` line carries the cell's per-layer metrics read from
    the trace, the device's busy and window seconds, the breakdown, and the
    numbers compared beside their limits as its last key."""
    import json

    from bench import harness
    cell = harness.load_cell("rgat-am.train_full", seed=1, seconds=1,
                             trace=True, cache_dir=tmp_path)
    dt = trace_reduce.reduce_planes(_planes())
    layer = {"window_s": 0.1, "steps": 2, "device_kind": "TPU v5 lite",
             "work": {"segment_mm": {"flops": 1e9, "bytes": 1e7},
                      "traversal": {"flops": 1e8, "bytes": 1e6},
                      "model_flops": 2e9}}
    out = harness.Outcome(attempted=2, failed=0, metrics={},
                          checks=[harness.Check("loss_gap", 1e-6, 1e-4)],
                          memory_peak_bytes=5, layer=layer)

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    line = harness.result_line(harness.load_benchmark(), cell, out, [Dev()],
                               dt)
    assert line["correct"] and list(line)[-1] == "checks"
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["idle.train_full"] == pytest.approx(60.0)
    # least time 2 steps x 1e7 B / 819 GB/s over the 25 ms of _mm_kernel:
    # the reader matches instruction names, and "_mm_kernel" is none of the
    # program's wrappers, so no segment-GEMM share is read
    assert "segment_mm_roofline.train_full" not in m
    assert m["mfu.train_full"] == pytest.approx(
        100 * 2e9 * 2 / 0.1 / 197e12)
    assert line["device"]["busy_s"] == pytest.approx(0.04)
    assert line["breakdown"]["device_ops"][0][0] == "_mm_kernel"
    json.dumps(line)
