"""Synthetic heterographs with the statistics of Hector's Table 3 datasets.

A copy of the program's ``synthetic_heterograph`` arithmetic, owned by the
benchmark so that no later change to the program moves the yardstick. The
graph is data: raw ``(src, dst, etype, node_type)`` arrays that the harness
hands to the program's ``HeteroGraph.from_edges`` and to the plain
references alike.

A configuration fixes its graph with ``graph_seed``; the run's ``--seed``
draws weights, features and traffic, never the graph, so every seed does
the same amount of work. Generated arrays are cached under
``bench/.cache/graphs`` (ignored by git), so only a cell's first run in a
checkout pays for generation.
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
from typing import Dict, Optional

import numpy as np

# Published statistics (Hector, arXiv:2301.06284, Table 3, after DGL/OGB
# preprocessing): name -> (num_nodes, num_ntypes, num_edges, num_etypes).
TABLE3 = {
    "aifb": (7_300, 7, 49_000, 104),
    "am": (1_900_000, 7, 5_700_000, 108),
    "bgs": (95_000, 27, 673_000, 122),
    "biokg": (94_000, 5, 4_800_000, 51),
    "fb15k": (15_000, 1, 620_000, 474),
    "mag": (1_900_000, 4, 21_000_000, 4),
    "mutag": (27_000, 5, 148_000, 50),
    "wikikg2": (2_500_000, 1, 16_000_000, 535),
}

# Entity-compaction ratios (#unique (src, etype) pairs / #edges, Fig. 10):
# am 0.57 and fb15k 0.26 are in the paper's text, the rest read off Fig. 10.
TABLE3_COMPACTION = {
    "aifb": 0.80, "am": 0.57, "bgs": 0.75, "biokg": 0.45,
    "fb15k": 0.26, "mag": 0.34, "mutag": 0.70, "wikikg2": 0.55,
}

_FIELDS = ("src", "dst", "etype", "node_type")


def synthetic_edges(num_nodes: int, num_edges: int, num_ntypes: int,
                    num_etypes: int, seed: int = 0,
                    degree_alpha: float = 1.2,
                    target_compaction: Optional[float] = None
                    ) -> Dict[str, np.ndarray]:
    """Power-law heterograph with (N, E, #ntypes, #etypes) statistics.

    Nodes are presorted by type. Destinations follow a Pareto popularity;
    with ``target_compaction`` every edge draws its (src, etype) from a pool
    of ``ratio * E`` pairs, each used at least once, which reproduces the
    source reuse of the real datasets."""
    rng = np.random.default_rng(seed)
    props = rng.dirichlet(np.full(num_ntypes, 2.0))
    counts = np.maximum(1, (props * num_nodes).astype(np.int64))
    counts[-1] = max(1, num_nodes - int(counts[:-1].sum()))
    node_type = np.repeat(np.arange(num_ntypes, dtype=np.int32),
                          counts)[:num_nodes]
    node_type = np.sort(node_type)
    pop = rng.pareto(degree_alpha, size=num_nodes) + 1.0
    pop /= pop.sum()
    dst = rng.choice(num_nodes, size=num_edges, p=pop).astype(np.int32)
    if target_compaction is None:
        src = rng.integers(0, num_nodes, size=num_edges, dtype=np.int32)
        etype = rng.integers(0, num_etypes, size=num_edges, dtype=np.int32)
    else:
        u = max(1, int(num_edges * target_compaction))
        pool_src = rng.integers(0, num_nodes, size=u, dtype=np.int32)
        pool_et = rng.integers(0, num_etypes, size=u, dtype=np.int32)
        pick = np.concatenate([
            np.arange(u, dtype=np.int64),
            rng.integers(0, u, size=max(0, num_edges - u)),
        ])[:num_edges]
        src, etype = pool_src[pick], pool_et[pick]
    return {"src": src.astype(np.int32), "dst": dst,
            "etype": etype.astype(np.int32),
            "node_type": node_type.astype(np.int32)}


def graph_params(graph_cfg: dict) -> dict:
    """The generator's arguments for a configuration's ``graph`` entry."""
    return {"num_nodes": int(graph_cfg["num_nodes"]),
            "num_edges": int(graph_cfg["num_edges"]),
            "num_ntypes": int(graph_cfg["num_ntypes"]),
            "num_etypes": int(graph_cfg["num_etypes"]),
            "seed": int(graph_cfg["graph_seed"]),
            "degree_alpha": float(graph_cfg["degree_alpha"]),
            "target_compaction": graph_cfg.get("compaction")}


def load_graph(graph_cfg: dict, cache_dir: pathlib.Path
               ) -> Dict[str, np.ndarray]:
    """The configuration's edge arrays, from the cache when present.

    The cache key is a hash of the generator's arguments; entries are
    written to a temporary name and renamed, so a run killed mid-write
    never leaves a partial graph behind."""
    params = graph_params(graph_cfg)
    key = hashlib.sha1(json.dumps(params, sort_keys=True).encode()
                       ).hexdigest()[:16]
    path = pathlib.Path(cache_dir) / f"graph-{key}.npz"
    if path.exists():
        with np.load(path) as z:
            return {f: z[f] for f in _FIELDS}
    arrays = synthetic_edges(**params)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.stem}.tmp{os.getpid()}.npz")
    try:
        np.savez(tmp, **arrays)
        os.replace(tmp, path)
    finally:
        if tmp.exists():
            tmp.unlink()
    return arrays
