"""What every model's work counts share: the graph's statistics, the least
time of a family's work, and the graphs they are counted on."""
import numpy as np
import pytest

from bench import graphgen, peaks, work

# 3 nodes, 2 relations: (src, dst, etype)
EDGES = np.array([(0, 1, 0), (0, 2, 0), (1, 2, 1), (0, 1, 1)], np.int32)


def _stats():
    return work.graph_stats(EDGES[:, 0], EDGES[:, 1], EDGES[:, 2], 3)


def test_graph_stats_by_hand():
    # unique (etype, src): (0,0) (1,1) (1,0); unique (etype, dst): four
    assert _stats() == {"E": 4, "U": 3, "D": 4, "Nd": 2, "Nsd": 3, "N": 3}


def test_least_seconds_picks_the_binding_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_seconds({"flops": 197e12, "bytes": 1.0}, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.least_seconds({"flops": 1.0, "bytes": 819e9}, p)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def test_graphgen_matches_table3_and_caches(tmp_path):
    import json
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    for name in ("rgat-am", "rgcn-mag"):
        g = json.loads((root / "configs" / f"{name}.json").read_text())[
            "graph"]
        n, nt, e, et = graphgen.TABLE3[g["dataset"]]
        assert (g["num_nodes"], g["num_ntypes"], g["num_edges"],
                g["num_etypes"]) == (n, nt, e, et)
        assert g["compaction"] == graphgen.TABLE3_COMPACTION[g["dataset"]]
    cfg = {"num_nodes": 500, "num_edges": 2000, "num_ntypes": 3,
           "num_etypes": 4, "compaction": 0.5, "degree_alpha": 1.2,
           "graph_seed": 7}
    first = graphgen.load_graph(cfg, tmp_path)
    again = graphgen.load_graph(cfg, tmp_path)
    assert len(list(tmp_path.glob("graph-*.npz"))) == 1
    for k in first:
        assert np.array_equal(first[k], again[k])
    assert np.all(np.diff(first["node_type"]) >= 0)
