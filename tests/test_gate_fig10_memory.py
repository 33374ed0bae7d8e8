"""Paper Fig. 10: compact materialization's memory footprint.

For an HGT-like layer (two edgewise hidden-dim tensors, three nodewise
ones, the typed weights) on each Table 3 dataset at its CPU-reduced
scale, the footprint ratio of compact to vanilla materialization is at
least the entity-compaction ratio: nodewise data and weights do not
shrink, so the whole footprint shrinks by less than the edgewise part.
"""
import pytest

from repro.core.graph import CPU_REDUCED_SCALES, TABLE3_DATASETS, table3_graph

D = 64


@pytest.mark.parametrize("dataset", list(TABLE3_DATASETS))
def test_footprint_ratio_at_least_compaction(dataset):
    hg = table3_graph(dataset, scale=CPU_REDUCED_SCALES[dataset], seed=0)
    e, u, n, r = hg.num_edges, hg.num_unique, hg.num_nodes, hg.num_etypes
    edge_vanilla = 2 * e * D * 4
    edge_compact = 2 * u * D * 4
    nodewise = 3 * n * D * 4              # k, q, v
    weights = (3 * hg.num_ntypes + 2 * r) * D * D * 4
    ratio = (edge_compact + nodewise + weights) / (
        edge_vanilla + nodewise + weights)
    assert ratio >= u / e - 1e-9, (
        f"{dataset}: footprint ratio {ratio:.3f} below entity compaction "
        f"{u / e:.3f}")
