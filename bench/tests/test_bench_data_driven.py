"""The harness is driven by data: a configuration, a traffic mix, a cell
and a per-layer metric are new files and entries, with no edit to an
existing file, and ``bench/run.py`` finds each by name. So does a new
model with node-typed weights: its reference and counts are new files."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import bench_tiny as tiny
from bench import harness, work
from bench.runners import common

DATA = pathlib.Path(__file__).with_name("data")

NEW_METRIC = '''
def read(data):
    return 100.0 * len(data["queue_ms"]) if data.get("queue_ms") else None
'''


def _checkout(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark's own files (what a checkout of ``paths``
    holds) with a new configuration, mix, cell and metric dropped in, and
    a new model, single-head HGT, whose K/Q/V weights are indexed by node
    type: its reference, counts, configuration, limits and cell."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    cfg = json.loads((root / "bench/configs/rgat-am.json").read_text())
    cfg["name"] = "rgcn-am"
    cfg["model"]["name"] = cfg["reference"] = "rgcn"
    (root / "bench/configs/rgcn-am.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/serve_slow.json").write_text(json.dumps(dict(
        json.loads((root / "bench/traffic/serve_poisson.json").read_text()),
        rate_rps=8)))
    shutil.copy(root / "bench/limits/rgcn-mag.serve_poisson.json",
                root / "bench/limits/rgcn-am.serve_slow.json")
    (root / "bench/metrics/served.serve_slow.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "rgcn-am", "source": "test",
                             "file": "bench/configs/rgcn-am.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "rgcn-am.serve_slow",
                               "config": "rgcn-am", "traffic": "serve_slow",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "request_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["rgcn-am.serve_slow"]})
    bench["per_layer"].append({"name": "served.serve_slow", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "serve runtime",
                               "moves": "request_p95_ms",
                               "workloads": ["rgcn-am.serve_slow"]})

    hgt = json.loads((root / "bench/configs/rgat-am.json").read_text())
    hgt["name"] = "hgt-am"
    hgt["model"]["name"] = hgt["reference"] = "hgt"
    (root / "bench/configs/hgt-am.json").write_text(json.dumps(hgt))
    shutil.copy(DATA / "hgt_reference.py", root / "bench/reference/hgt.py")
    shutil.copy(DATA / "hgt_counts.py", root / "bench/counts/hgt.py")
    shutil.copy(root / "bench/limits/rgat-am.train_full.json",
                root / "bench/limits/hgt-am.train_full.json")
    bench["configs"].append({"name": "hgt-am", "source": "test",
                             "file": "bench/configs/hgt-am.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "hgt-am.train_full",
                               "config": "hgt-am", "traffic": "train_full",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rgat-am.train_full" in m.get("workloads", ()):
            m["workloads"].append("hgt-am.train_full")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = _checkout(tmp_path)
    # run.py of the copy loads the new cell's files, then stops: no TPU
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(root / "bench/run.py"), "--workload",
         "rgcn-am.serve_slow", "--seed", str(tiny.SEED), "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "JAX finds no TPU" in proc.stderr, proc.stderr[-2000:]
    assert not proc.stdout.strip()          # no result line

    # the new cell runs end to end, and the new metric reads its data
    c = tiny.cell("rgcn-am.serve_slow", tmp_path, root=root)
    c.config["graph"].update(num_nodes=1000, num_edges=3000,
                             num_etypes=12)
    c.traffic.update(rate_rps=8)
    out = tiny.run(c)
    assert tiny.correct(out), out.checks
    bench = harness.load_benchmark(root)
    layer = harness.per_layer(bench, c, dict(out.layer, trace=None))
    assert layer["served.serve_slow"]["value"] > 0


def _hgt_cell(tmp_path):
    return tiny.cell("hgt-am.train_full", tmp_path,
                     root=_checkout(tmp_path))


def test_node_typed_model_joins_by_new_files(tmp_path):
    """HGT's weights W_K, W_Q, W_V lead with the node-type count; its
    reference reads node types, and its cell runs correct through the
    same runner, with its counts found by name."""
    c = _hgt_cell(tmp_path)
    out = tiny.run(c)
    assert tiny.correct(out), out.checks
    arrays = common.load_arrays(c)
    params, _, _ = common.make_inputs(c, int(arrays["node_type"].size))
    t = c.config["graph"]["num_ntypes"]
    assert params[0]["W_K"].shape == (t, 64, 64)
    stats = work.graph_stats(arrays["src"], arrays["dst"], arrays["etype"],
                             int(arrays["node_type"].size))
    w = work.step_work(c.config, stats, True, c.root)
    assert w["model_flops"] > 0 and all(
        w[f]["flops"] > 0 and w[f]["bytes"] > 0
        for f in ("segment_mm", "traversal"))


def test_node_types_moved_are_not_correct(tmp_path, monkeypatch):
    """The program given other node types than the reference reads: each
    node takes the next type (the last type keeps its own, since the
    program needs nodes sorted by type). The comparison must see it."""
    from repro.core.graph import HeteroGraph
    orig = HeteroGraph.from_edges

    def from_edges(*args, node_type=None, num_ntypes=1, **kw):
        moved = np.minimum(np.asarray(node_type) + 1, num_ntypes - 1)
        return orig(*args, node_type=moved, num_ntypes=num_ntypes, **kw)
    monkeypatch.setattr(HeteroGraph, "from_edges", staticmethod(from_edges))
    out = tiny.run(_hgt_cell(tmp_path))
    assert not tiny.correct(out), out.checks


def test_traced_run_without_counts_names_the_file(tmp_path):
    c = _hgt_cell(tmp_path)
    (c.root / "bench/counts/hgt.py").unlink()
    c.trace = True
    with pytest.raises(FileNotFoundError, match="bench/counts/hgt.py"):
        tiny.run(c)


def test_run_without_a_tpu_prints_no_result(tmp_path):
    """Without a TPU, and in a directory holding only ``BENCHMARK.json``
    and the benchmark's files, a run exits non-zero and prints nothing on
    standard output."""
    root = tmp_path / "bare"
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    for name in [w["name"] for w in harness.load_benchmark()["workloads"]]:
        proc = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", name, "--seed",
             "1", "--seconds", "1", "--trace", "1"], cwd=root, env=env,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0 and not proc.stdout.strip()
    assert jax.devices()[0].platform == "cpu"
