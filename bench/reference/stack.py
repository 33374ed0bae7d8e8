"""Model-agnostic reference runners: the layer stack, the full-graph loss
and AdamW steps, and the sampled-block forward.

A model's plain reference is one file, ``bench/reference/<reference>.py``
(``rgat``, ``rgcn``; named by the configuration's ``reference`` key),
which provides

* ``param_shapes(dims, num_etypes, num_ntypes)``: the parameter pytree,
  one dict per layer of name -> shape; a weight indexed by relation leads
  with ``num_etypes``, one indexed by node type with ``num_ntypes``;
* ``layer(p, x, dg, num_nodes, chunk, precision)``: one layer over the
  relation blocks of ``common.EdgeGraph``. ``dg`` is
  ``common.device_graph``'s dict: ``src`` and ``dst`` blocks, and
  ``node_type`` [num_nodes] in full-graph training. A layer reads the
  number of node types from its weights' leading axis.

Layers are joined by relu. Its work counts are ``bench/counts/`` files
(``bench/work.py``).
"""
from __future__ import annotations

import functools
import pathlib
from typing import Dict, List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import common as C


def model(name: str, root: pathlib.Path = harness.ROOT):
    """The reference module for a configuration's ``reference`` key, from
    the checkout at ``root``."""
    return harness.module("reference", name, root)


def forward(mod, params, x, dg, num_nodes: int, chunk: int,
            precision: str):
    h = x
    last = len(params) - 1
    for i, p in enumerate(params):
        h = mod.layer(p, h, dg, num_nodes, chunk, precision)
        if i < last:
            h = jnp.maximum(h, 0.0)
    return h


@functools.lru_cache(maxsize=None)
def _loss_and_grad(mod, num_nodes: int, chunk: int, precision: str):
    def loss(params, x, labels, rows, dg):
        logits = forward(mod, params, x, dg, num_nodes, chunk, precision)
        return C.cross_entropy(logits[rows], labels[rows])
    return jax.jit(jax.value_and_grad(loss))


def train_steps(mod, params, x, labels, rows, g: C.EdgeGraph,
                hp: C.AdamWConfig, steps: int, precision: str = "highest"):
    """``steps`` AdamW steps of full-graph training with the loss over the
    ``rows`` nodes. Returns host arrays: ``losses`` [steps], ``grad1`` (the
    first step's clipped gradient, as the optimizer takes it), and
    ``params`` after every step (``params[0]`` is the start)."""
    dg = C.device_graph(g)
    fn = _loss_and_grad(mod, g.num_nodes, g.chunk, precision)
    rows = jnp.asarray(rows)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    to_host = functools.partial(jax.tree.map, np.asarray)
    out = {"losses": [], "grad1": None, "params": [to_host(params)]}
    for step in range(1, steps + 1):
        loss, grads = fn(params, x, labels, rows, dg)
        params, mu, nu, clipped = C.adamw_step(params, grads, mu, nu, step,
                                               hp)
        out["losses"].append(float(loss))
        if step == 1:
            out["grad1"] = to_host(clipped)
        out["params"].append(to_host(params))
    return out


# ---------------------------------------------------------------------------
# sampled blocks (serving)
# ---------------------------------------------------------------------------
def _bucket(n: int) -> int:
    return 1 << max(4, int(n - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _layer_fn(mod, num_nodes: int, chunk: int, precision: str):
    return jax.jit(lambda p, x, dg: mod.layer(p, x, dg, num_nodes, chunk,
                                              precision))


def hop_sizes(hops: Sequence[Dict], num_etypes: int):
    """Power-of-two node count and relation block length covering
    ``hops`` (one hop's dicts from many batches), so that one compiled
    layer serves them all."""
    n = max(h["num_nodes"] for h in hops)
    m = max(int(np.bincount(np.asarray(h["etype"], np.int64),
                            minlength=num_etypes).max()) if len(h["etype"])
            else 1 for h in hops)
    return _bucket(n + 1), _bucket(m)


def forward_blocks(mod, params, x0: np.ndarray, hops: Sequence[Dict],
                   num_etypes: int, sizes, precision: str = "highest"):
    """Logits of one sampled batch.

    ``x0``: input features of hop 0's nodes. ``hops`` (innermost first):
    dicts with block-local ``src``, ``dst``, ``etype``, ``num_nodes`` and
    ``dst_local`` (the rows that feed the next hop, or the final frontier).
    ``sizes``: per hop, the padded node count and relation block length
    (``hop_sizes``). Returns the final frontier's rows."""
    last = len(hops) - 1
    h = np.asarray(x0, np.float32)
    for i, (p, hop) in enumerate(zip(params, hops)):
        n_pad, m_pad = sizes[i]
        g = C.edge_graph(hop["src"], hop["dst"], hop["etype"], n_pad,
                         num_etypes, block=m_pad)
        x = np.zeros((g.num_nodes, h.shape[1]), np.float32)
        x[:h.shape[0]] = h
        out = _layer_fn(mod, g.num_nodes, g.chunk, precision)(
            p, jnp.asarray(x), C.device_graph(g))
        h = np.asarray(out)[np.asarray(hop["dst_local"])]
        if i < last:
            h = np.maximum(h, 0.0)
    return h


def host_params(params) -> List[Dict[str, np.ndarray]]:
    return [{k: np.asarray(v) for k, v in p.items()} for p in params]
