"""RGAT's work counts (``bench/counts/rgat.py``): hand-counted totals on a
tiny graph, counts that do not depend on how the program materializes or
which backend runs it, found by the configuration's ``reference`` key; and
the seed's weights for ``rgat-am``, which the node-type count must not
move."""
import jax
import numpy as np
import pytest

import bench_tiny as tiny
from bench import graphgen, work
from bench.counts import rgat
from bench.runners import common

# 3 nodes, 2 relations: (src, dst, etype)
EDGES = np.array([(0, 1, 0), (0, 2, 0), (1, 2, 1), (0, 1, 1)], np.int32)


def _stats():
    return work.graph_stats(EDGES[:, 0], EDGES[:, 1], EDGES[:, 2], 3)


def test_rgat_step_by_hand():
    w = rgat.step(_stats(), [2, 3], {"num_etypes": 2}, train=True)
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
    assert (rgat.step(raw, [8, 8, 4], {"num_etypes": 5})
            == rgat.step(work.graph_stats(hg.src, hg.dst, hg.etype, 300),
                         [8, 8, 4], {"num_etypes": 5}))
    # ... and never more rows than it materializes, compact or not
    lay = stack.layers[0].layouts
    rows = (lay.unique_seg if compact else lay.edge_seg).row_map.shape[0]
    assert raw["U"] <= rows and raw["E"] <= lay.edge_seg.row_map.shape[0]


def test_counts_are_found_by_the_reference_key():
    cfg = {"reference": "rgat", "graph": {"num_etypes": 2},
           "model": {"layers": 1, "dim": 2, "hidden": 2, "classes": 3}}
    assert (work.step_work(cfg, _stats(), True)
            == rgat.step(_stats(), [2, 3], {"num_etypes": 2}, True))
    with pytest.raises(FileNotFoundError, match="bench/counts/rgcn.py"):
        work.step_work(dict(cfg, reference="rgcn"), _stats(), True)


def test_rgat_inputs_keep_their_layout(tmp_path):
    """``make_inputs`` for ``rgat-am`` draws from the shapes tuple it drew
    from before node-type counts were passed: the same pytree shapes and
    key order, so the same arrays for every seed."""
    c = tiny.cell("rgat-am.train_full", tmp_path)
    r = c.config["graph"]["num_etypes"]
    before = tuple(
        (("W_rel", (r, k, n)), ("w_att_dst", (r, n)), ("w_att_src", (r, n)))
        for k, n in ((64, 64), (64, 11)))
    n = c.config["graph"]["num_nodes"]
    params, feats, labels = common.make_inputs(c, n)
    assert [list(p) for p in params] == [[k for k, _ in layer]
                                         for layer in before]
    assert [{k: v.shape for k, v in p.items()} for p in params] == [
        dict(layer) for layer in before]
    key = jax.random.key(tiny.harness.seed_int(c.seed, common.TAG_INPUTS))
    want = common._input_fn(before, n, 64, 11)(key)
    for a, b in zip(jax.tree.leaves((params, feats, labels)),
                    jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
