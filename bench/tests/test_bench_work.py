"""Work counts: hand-counted totals on a tiny graph, and counts that do not
depend on how the program materializes or which backend runs it."""
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


def test_rgat_step_by_hand():
    w = work.rgat_step(_stats(), [2, 3], num_etypes=2, train=True)
    # forward GEMMs 2*(U*k*n + U*k + D*k) = 2*(18 + 6 + 8); backward dW the
    # same again, no dX for layer 0's features
    assert w["segment_mm"]["flops"] == 128
    # forward 4*(Nsd*k + R*k*n + 2*R*k + U*n + U + D) = 4*42, backward
    # 4*((U*n + U + D) + R*k*n + 2*R*k) = 4*36
    assert w["segment_mm"]["bytes"] == 4 * 42 + 4 * 36
    # 2*E*n + 3*E flops; 4*(E + U*n + Nd*n) bytes
    assert w["traversal"] == {"flops": 36, "bytes": 76}
    # forward 64 + 48 + 36 + 8 + 9, backward 64 + 48 + 48 + 16, loss 135
    assert w["model_flops"] == 165 + 176 + 135


def test_least_seconds_picks_the_binding_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_seconds({"flops": 197e12, "bytes": 1.0}, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.least_seconds({"flops": 1.0, "bytes": 819e9}, p)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("compact", [True, False])
def test_count_does_not_depend_on_the_program(compact, backend):
    from repro.core.graph import HeteroGraph
    from repro.core.module import HectorStack
    from repro.models import rgat_program
    a = graphgen.synthetic_edges(300, 1200, 3, 5, seed=1,
                                 target_compaction=0.5)
    raw = work.graph_stats(a["src"], a["dst"], a["etype"], 300)
    hg = HeteroGraph.from_edges(a["src"], a["dst"], a["etype"],
                                num_nodes=300, num_etypes=5,
                                node_type=a["node_type"], num_ntypes=3)
    stack = HectorStack([rgat_program(8, 8), rgat_program(8, 4)], hg,
                        compact=compact, backend=backend, tile=8,
                        node_block=8)
    # the program's own edge order gives the same count ...
    assert work.graph_stats(hg.src, hg.dst, hg.etype, 300) == raw
    assert (work.rgat_step(raw, [8, 8, 4], 5)
            == work.rgat_step(work.graph_stats(hg.src, hg.dst, hg.etype,
                                               300), [8, 8, 4], 5))
    # ... and never more rows than it materializes, compact or not
    lay = stack.layers[0].layouts
    rows = (lay.unique_seg if compact else lay.edge_seg).row_map.shape[0]
    assert raw["U"] <= rows and raw["E"] <= lay.edge_seg.row_map.shape[0]


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
