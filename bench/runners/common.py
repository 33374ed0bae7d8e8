"""Pieces both runners share: the graph, the compiled program, inputs made
on the device from the seed, and freeing the program before a reference
runs."""
from __future__ import annotations

import functools
import gc
import math
from typing import Dict, List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from bench import graphgen
from bench.harness import Cell, seed_int
from bench.reference import stack

# tags that keep the seed's draws for different purposes apart
TAG_INPUTS, TAG_SAMPLER, TAG_TRAFFIC, TAG_CHECK, TAG_WARM = 1, 2, 3, 4, 5


def dims(cfg: dict) -> List[int]:
    m = cfg["model"]
    return [m["dim"]] + [m["hidden"]] * (m["layers"] - 1) + [m["classes"]]


def load_arrays(cell: Cell) -> Dict[str, np.ndarray]:
    return graphgen.load_graph(cell.config["graph"],
                               cell.cache_dir / "graphs")


def compile_program(cell: Cell, arrays: Dict[str, np.ndarray], **kw):
    """The configuration's graph handed to ``HeteroGraph.from_edges`` and
    compiled by ``hector.compile``, the entry point users call."""
    import hector
    from repro.core.graph import HeteroGraph
    g = cell.config["graph"]
    hg = HeteroGraph.from_edges(
        arrays["src"], arrays["dst"], arrays["etype"],
        num_nodes=int(arrays["node_type"].size),
        num_etypes=int(g["num_etypes"]), node_type=arrays["node_type"],
        num_ntypes=int(g["num_ntypes"]))
    m, c = cell.config["model"], cell.config["compile"]
    return hector.compile(m["name"], hg, layers=m["layers"], dim=m["dim"],
                          hidden=m["hidden"], classes=m["classes"], **c,
                          **kw)


def _fan_in(name: str, shape: Sequence[int]) -> int:
    # matrices [R, k, n] / [k, n] scale by k; per-relation vectors [R, n]
    # (lower-case ``w_`` names) by n: the program's own initialization
    return int(shape[-1] if name.startswith("w_") else shape[-2])


@functools.lru_cache(maxsize=None)
def _input_fn(shapes: tuple, num_nodes: int, dim: int, classes: int):
    def gen(key):
        ks = jax.random.split(key, len(shapes) + 2)
        params = []
        for i, layer in enumerate(shapes):
            lk = jax.random.split(ks[i], len(layer))
            params.append({
                name: jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(_fan_in(name, shape))
                for k, (name, shape) in zip(lk, layer)})
        feats = jax.random.normal(ks[-2], (num_nodes, dim), jnp.float32)
        labels = jax.random.randint(ks[-1], (num_nodes,), 0, classes,
                                    jnp.int32)
        return params, feats, labels
    return jax.jit(gen)


def make_inputs(cell: Cell, num_nodes: int):
    """Weights, features and labels for the run's seed, made on the device
    in one jitted call: the same seed gives the same arrays."""
    cfg = cell.config
    g = cfg["graph"]
    mod = stack.model(cfg["reference"], cell.root)
    shapes = tuple(tuple(sorted((k, tuple(v)) for k, v in layer.items()))
                   for layer in mod.param_shapes(
                       dims(cfg), g["num_etypes"], g["num_ntypes"]))
    key = jax.random.key(seed_int(cell.seed, TAG_INPUTS))
    return _input_fn(shapes, num_nodes, cfg["model"]["dim"],
                     cfg["model"]["classes"])(key)


def check_param_structure(compiled, params) -> None:
    """The benchmark's weights must have the program's own layout."""
    want = jax.eval_shape(compiled.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{want} vs {got}")


def loss_rows(cell: Cell, num_nodes: int) -> np.ndarray:
    """The nodes the training loss is taken over (the mix's ``loss_rows``:
    ``"all"``)."""
    if cell.traffic["loss_rows"] != "all":
        raise ValueError(f"loss_rows {cell.traffic['loss_rows']!r}")
    return np.arange(num_nodes, dtype=np.int32)


def free_device() -> None:
    """Drop what the program held once its references are gone."""
    gc.collect()
    jax.clear_caches()


def percentile_nearest(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) over every value."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    k = max(0, int(math.ceil(q / 100.0 * v.size)) - 1)
    return float(v[k])
