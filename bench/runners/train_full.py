"""Full-graph training: ``FullGraphTrainer.step`` back to back.

Set-up builds one trainer (graph, ``hector.compile``, features and labels
on the device, AdamW state from the seed's weights) and drives it through
its first ``first_steps`` steps with the same call the window uses; those
steps compile the step program and give the readings that decide
``correct``. The window then keeps calling ``step`` on that same trainer,
one step in flight behind the host, until ``--seconds`` have passed, and
closes on ``block_until_ready`` of the last state.

``full_step_ms`` is the window's length over the steps completed in it.
After the window, with the program's state freed, the plain reference
repeats the first steps from the same weights, and three numbers are
compared (each the worst over the steps or the parameter leaves):

* ``loss_gap``: |loss - reference loss| / |reference loss|;
* ``grad_gap``: per leaf, the gap between the norms of the first gradient
  as the optimizer takes it (recovered from AdamW's first moment after
  step 1) and the reference's;
* ``update_gap``: per leaf, the gap between the norms of the parameters'
  change over the first steps, as the state handed to the window holds it.

Norm gaps are relative to the larger of the reference leaf's norm and the
median leaf's. Leaves whose reference gradient is under a thousandth of the
median leaf's are left out (they move by round-off alone).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np
import jax

from bench import work
from bench.runners import common
from bench.harness import Check, Cell, Outcome, log, memory_peak_bytes
from bench.reference import common as RC
from bench.reference import stack

EXCLUDE_BELOW = 1e-3


def _leaves(tree) -> List[np.ndarray]:
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def norm_gaps(prog: List[np.ndarray], ref: List[np.ndarray],
              keep: List[bool]) -> List[float]:
    """Per leaf: |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖ over kept)."""
    rn = np.asarray([np.linalg.norm(r) for r in ref])
    med = float(np.median(rn[keep])) if any(keep) else 0.0
    out = []
    for p, r, k, n in zip(prog, ref, keep, rn):
        if k:
            out.append(abs(float(np.linalg.norm(p)) - float(n))
                       / max(float(n), med, 1e-30))
    return out


def diff_norms(prog, ref, keep) -> List[float]:
    """Per leaf: ‖prog - ref‖ / ‖ref‖ (printed beside the checks)."""
    return [float(np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30))
            for p, r, k in zip(prog, ref, keep) if k]


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The readings of one run: program against reference (or a control
    against the reference; both are dicts of ``losses``, ``grad1``,
    ``params`` after each of the first steps)."""
    n = len(ref["losses"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"][:n], ref["losses"]))
    g_ref, g_prog = _leaves(ref["grad1"]), _leaves(prog["grad1"])
    gn = np.asarray([np.linalg.norm(g) for g in g_ref])
    keep = list(gn >= EXCLUDE_BELOW * np.median(gn))
    d_ref = [b - a for a, b in zip(_leaves(ref["params"][0]),
                                   _leaves(ref["params"][n]))]
    d_prog = [b - a for a, b in zip(_leaves(prog["params"][0]),
                                    _leaves(prog["params"][n]))]
    return {
        "loss_gap": loss_gap,
        "grad_gap": max(norm_gaps(g_prog, g_ref, keep)),
        "update_gap": max(norm_gaps(d_prog, d_ref, keep)),
        "grad_diff": max(diff_norms(g_prog, g_ref, keep)),
        "update_diff": max(diff_norms(d_prog, d_ref, keep)),
        "leaves_left_out": float(len(keep) - sum(keep)),
    }


CHECKED = ("loss_gap", "grad_gap", "update_gap")


class Program:
    """The system under test for one seed: one trainer and its state."""

    def __init__(self, cell: Cell, arrays, compiled=None):
        from repro.optim import AdamW
        from repro.train import FullGraphTrainer
        self.cell = cell
        self.compiled = compiled if compiled is not None else \
            common.compile_program(cell, arrays)
        n = int(arrays["node_type"].size)
        params, feats, labels = common.make_inputs(cell, n)
        common.check_param_structure(self.compiled, params)
        self.rows = common.loss_rows(cell, n)
        self.trainer = FullGraphTrainer(
            self.compiled, feats, labels, self.rows,
            opt=AdamW(**cell.config["optimizer"]), log=None)
        self.state = self.trainer.init_state(params)
        self.b1 = float(cell.config["optimizer"]["b1"])

    def first_steps(self, steps: int) -> Dict:
        """The first steps through the window's own call, with host copies
        of what the comparison reads."""
        host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        out = {"losses": [], "params": [host(self.state.params)]}
        for i in range(steps):
            self.state, m = self.trainer.step(self.state)
            out["losses"].append(float(m["loss"]))
            if i == 0:
                out["grad1"] = jax.tree.map(
                    lambda m1: np.asarray(m1) / (1.0 - self.b1),
                    self.state.mu)
            out["params"].append(host(self.state.params))
        return out

    def close(self) -> None:
        self.trainer = self.state = self.compiled = None


def reference(cell: Cell, arrays, steps: int, precision: str = "highest",
              rows=None) -> Dict:
    """The plain reference's first steps from the seed's weights."""
    cfg = cell.config
    n = int(arrays["node_type"].size)
    params, feats, labels = common.make_inputs(cell, n)
    g = RC.edge_graph(arrays["src"], arrays["dst"], arrays["etype"], n,
                      cfg["graph"]["num_etypes"],
                      node_type=arrays["node_type"])
    rows = common.loss_rows(cell, n) if rows is None else rows
    return stack.train_steps(stack.model(cfg["reference"], cell.root),
                             params, feats, labels, rows, g,
                             RC.AdamWConfig.from_config(cfg["optimizer"]),
                             steps, precision)


def run(cell: Cell, devices, counter) -> Outcome:
    steps = int(cell.traffic["first_steps"])
    arrays = common.load_arrays(cell)
    layer = {}
    if cell.trace:      # before set-up, so a model with no counts fails fast
        stats = work.graph_stats(arrays["src"], arrays["dst"],
                                 arrays["etype"],
                                 int(arrays["node_type"].size))
        layer.update(work=work.step_work(cell.config, stats, True,
                                         cell.root),
                     device_kind=devices[0].device_kind)
    prog = Program(cell, arrays)
    first = prog.first_steps(steps)
    setup_s = time.perf_counter() - cell.t_start
    log(f"[setup] {setup_s:.3f} s; first losses {first['losses']}")

    compiles0, traces0 = counter.count, prog.trainer.step_exec.trace_count
    losses: List = []
    with cell.window():
        t0 = time.perf_counter()
        prev = None
        while True:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                prog.state, m = prog.trainer.step(prog.state)
            losses.append(m["loss"])
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.wait_step"):
                    prev.block_until_ready()
            prev = m["loss"]
            if time.perf_counter() - t0 >= cell.seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait_step"):
            jax.block_until_ready(prog.state)
        t1 = time.perf_counter()
    n = len(losses)
    window_compiles = (counter.count - compiles0
                       + prog.trainer.step_exec.trace_count - traces0)
    values = np.asarray([float(x) for x in losses])
    failed = int(np.sum(~np.isfinite(values)))
    step_ms = (t1 - t0) * 1e3 / n
    log(f"[window] {n} steps in {t1 - t0:.3f} s: {step_ms:.3f} ms a step; "
        f"last loss {values[-1]!r}; {failed} non-finite")
    peak = memory_peak_bytes(devices)
    prog.close()
    del prog
    common.free_device()

    t_ref = time.perf_counter()
    ref = reference(cell, arrays, steps)
    readings = compare(first, ref)
    log(f"[reference] {time.perf_counter() - t_ref:.3f} s; losses "
        f"{ref['losses']}; readings {readings}")
    checks = [Check(k, readings[k], cell.limit(k)) for k in CHECKED]

    layer.update(window_s=t1 - t0, steps=n)
    return Outcome(attempted=n, failed=failed,
                   metrics={"full_step_ms": step_ms, "setup_s": setup_s},
                   checks=checks, memory_peak_bytes=peak, layer=layer,
                   window_compiles=window_compiles)
