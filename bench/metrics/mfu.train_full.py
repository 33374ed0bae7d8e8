"""Model FLOP utilization (%) of full-graph training: the model FLOPs of
the window's steps (forward and backward, ``bench/work.py``) over the
traced window's length and the chip's bf16 peak."""
from bench import peaks


def read(data):
    w, trace = data.get("work"), data["trace"]
    if w is None or trace.window_s <= 0:
        return None
    peak = peaks.peaks_for(data["device_kind"])["bf16_flops_per_s"]
    return 100.0 * w["model_flops"] * data["steps"] / trace.window_s / peak
