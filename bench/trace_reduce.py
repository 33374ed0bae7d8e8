"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an operation runs on a
  device, inside the window the benchmark marked with its
  ``bench.window`` annotation; idle share is 1 minus busy over the window;
* per-operation device time, summed over each device and averaged over the
  devices, keyed by the op's HLO instruction name and result type (the
  trace names an op by its whole HLO text);
* idle gaps: every stretch of the window with no device operation, named
  after the benchmark's own host annotation (``bench.*``) that overlaps it
  most, or ``no_host_span``.

Times are in seconds. All planes of one trace share a clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
PREFIX = "bench."
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OP = re.compile(r"^(%?[^ ]+)( = [^ ]+)?")
# device lines that repeat what the op line holds, at a coarser grain
_NOT_OPS = ("XLA Modules", "Steps", "Framework Name Scope", "Framework Ops",
            "Source code", "XLA TraceMe", "Launch Stats", "Sparse Core",
            "Async XLA Ops", "Scalar Unit", "TC Overlay")


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                   # averaged over devices
    num_devices: int
    op_seconds: Dict[str, float]    # per op name, averaged over devices
    gaps: List[Tuple[str, float]]   # (host span name, seconds), all gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def top_ops(self, n: int = 10) -> List[List]:
        items = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in items[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        by: Dict[str, float] = {}
        for name, s in self.gaps:
            by[name] = by.get(name, 0.0) + s
        items = sorted(by.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in items[:n]]

    def family_seconds(self, patterns: Sequence[str]) -> float:
        """Device seconds of the ops whose instruction name (the part
        before ``=``) matches any pattern."""
        rx = [re.compile(p) for p in patterns]
        return sum(s for name, s in self.op_seconds.items()
                   if any(r.search(name.split(" = ")[0]) for r in rx))


def op_name(text: str) -> str:
    """``%fusion.55 = f32[3231104,64]{...} fusion(...)`` ->
    ``%fusion.55 = f32[3231104,64]{...}``."""
    m = _OP.match(text)
    return m.group(0) if m else text


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_planes(planes) -> DeviceTrace:
    """``planes``: (name, [(line name, [(event, start_ns, dur_ns)])])."""
    host_spans: List[Tuple[str, float, float]] = []
    devices: List[List[Tuple[str, float, float]]] = []
    for pname, lines in planes:
        if pname.startswith("/host"):
            for _, evs in lines:
                host_spans += [(n, s, s + d) for n, s, d in evs
                               if n.startswith(PREFIX)]
        elif _DEVICE.match(pname):
            names = [ln for ln, _ in lines]
            keep = ["XLA Ops"] if "XLA Ops" in names else [
                n for n in names if n not in _NOT_OPS]
            devices.append([(op_name(n), s, s + d) for ln, evs in lines
                            if ln in keep for n, s, d in evs])
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = windows[-1]
    if not devices:
        raise ValueError("trace has no device plane")
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW]
    op_seconds: Dict[str, float] = {}
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    for d, ops in enumerate(devices):
        for n, s, e in ops:
            for cs, ce in _clip([(s, e)], lo, hi):
                op_seconds[n] = op_seconds.get(n, 0.0) + (ce - cs) * 1e-9
        merged = _clip(merge([(s, e) for _, s, e in ops]), lo, hi)
        busy += sum(e - s for s, e in merged) * 1e-9
        if d == 0:
            gaps = _name_gaps(_complement(merged, lo, hi), spans)
    nd = len(devices)
    return DeviceTrace(window_s=(hi - lo) * 1e-9, busy_s=busy / nd,
                       num_devices=nd,
                       op_seconds={k: v / nd for k, v in op_seconds.items()},
                       gaps=gaps)


def _complement(merged, lo, hi):
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _name_gaps(gaps, spans) -> List[Tuple[str, float]]:
    """Name each gap after the host span overlapping it most."""
    if not spans:
        return [("no_host_span", (e - s) * 1e-9) for s, e in gaps]
    spans = sorted(spans, key=lambda t: t[1])
    starts = np.asarray([s for _, s, _ in spans])
    longest = np.maximum.accumulate([e for _, _, e in spans])
    out = []
    for gs, ge in gaps:
        best, best_ov = "no_host_span", 0.0
        # spans starting before the gap's end whose running end reaches it
        k = int(np.searchsorted(starts, ge))
        j = int(np.searchsorted(longest[:k], gs, side="right"))
        for name, s, e in spans[j:k]:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append((best, (ge - gs) * 1e-9))
    return out


def load(path: str) -> DeviceTrace:
    """Reduce one ``.xplane.pb`` file (or the newest under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    planes = [(pl.name, [(ln.name, list(_events(ln))) for ln in pl.lines])
              for pl in pd.planes]
    return reduce_planes(planes)

