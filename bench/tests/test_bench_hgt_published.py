"""HGT as published (``hgt_published``): the program through
``hector.compile`` against the plain reference
``bench/reference/hgt_published.py`` on seeded random weights, the heads
as H single-head computations side by side, the tiny ``hgt-bgs`` cell
through the harness, and the work counts by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
import hector
from bench import work
from bench.counts import hgt_published as counts
from bench.reference import common as C
from bench.reference import stack
from bench.runners.common import _fan_in

HEADS, WIDTH, CLASSES = 4, 32, 4        # 4 heads of 8, 2 layers


@pytest.fixture(scope="module")
def bgs():
    from repro.core.graph import CPU_REDUCED_SCALES, table3_graph
    return table3_graph("bgs", CPU_REDUCED_SCALES["bgs"])


@pytest.fixture(scope="module")
def case(bgs):
    """Weights drawn as the benchmark draws them, features, labels, and
    the reference's logits, loss and gradients (computed once)."""
    ref = stack.model("hgt_published")
    shapes = ref.param_shapes([WIDTH, WIDTH, CLASSES], bgs.num_etypes,
                              bgs.num_ntypes, heads=HEADS)
    key = jax.random.key(7)
    params = [{name: jax.random.normal(jax.random.fold_in(key, 64 * i + j),
                                       shape) / np.sqrt(_fan_in(name, shape))
               for j, (name, shape) in enumerate(sorted(layer.items()))}
              for i, layer in enumerate(shapes)]
    x = jax.random.normal(jax.random.key(8), (bgs.num_nodes, WIDTH))
    labels = jax.random.randint(jax.random.key(9), (bgs.num_nodes,), 0,
                                CLASSES)
    g = C.edge_graph(bgs.src, bgs.dst, bgs.etype, bgs.num_nodes,
                     bgs.num_etypes, node_type=bgs.node_type)
    dg = C.device_graph(g)

    def loss(p):
        with jax.default_matmul_precision("highest"):
            h = stack.forward(ref, p, x, dg, g.num_nodes, g.chunk, "highest")
        return C.cross_entropy(h, labels), h

    (l, h), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return dict(params=params, x=x, labels=labels, loss=l, logits=h,
                grads=grads)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_program_matches_reference(bgs, case, backend):
    """Logits, loss and every gradient leaf of the compiled program against
    the reference. Both are float32 at "highest"; they differ only in the
    order of their sums (per-type GEMM tiles against one einsum per type,
    the traversal kernels' per-block softmax against segment ops), which
    reads about 5e-7 relative here. The bounds, 1e-5, leave that twenty
    times the room, and a step in bfloat16 (3e-3 relative) would be far
    outside them."""
    comp = hector.compile("hgt_published", bgs, layers=2, dim=WIDTH,
                          hidden=WIDTH, classes=CLASSES, backend=backend,
                          tile=32, node_block=32, heads=HEADS)
    params = case["params"]
    want = jax.eval_shape(comp.init, jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert [jax.tree.map(jnp.shape, p) for p in params] == \
        [jax.tree.map(lambda s: s.shape, p) for p in want]

    def loss(p):
        with jax.default_matmul_precision("highest"):
            h = comp.apply(p, case["x"])
        return C.cross_entropy(h, case["labels"]), h

    (l, h), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert _rel(h, case["logits"]) < 1e-5
    assert abs(float(l) - float(case["loss"])) < 1e-5 * abs(float(l))
    for layer, (gp, gr) in enumerate(zip(grads, case["grads"])):
        assert sorted(gp) == sorted(gr)
        for name in gr:
            assert _rel(gp[name], gr[name]) < 1e-5, (layer, name)


@hector.model
def attention(g, e, n, in_dim, out_dim, heads):
    """The per-head part of an HGT layer, on given k, q, v."""
    dk = in_dim // heads
    W_att = g.weight("W_att", (heads, dk, dk), indexed_by="etype")
    W_msg = g.weight("W_msg", (heads, dk, dk), indexed_by="etype")
    w_prior = g.weight("w_prior", (heads,), indexed_by="etype")
    e["katt"] = e.src["k"] @ W_att
    e["msg"] = e.src["v"] @ W_msg
    e["att_raw"] = hector.dot(e["katt"], e.dst["q"], heads=heads) * w_prior
    e["att"] = hector.edge_softmax(e["att_raw"])
    n["agg"] = hector.aggregate(e["msg"], scale=e["att"])
    return n["agg"]


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_heads_are_single_heads_side_by_side(bgs, backend):
    """H heads of dk give the concatenation of H one-head computations,
    each on its dk columns of k, q, v with its own relation matrices and
    prior. Each head's arithmetic is the same in both, so the bound, 1e-6
    relative, is float32 round-off only."""
    from repro.core.module import HectorModule
    heads, dk = HEADS, WIDTH // HEADS
    rng = np.random.default_rng(3)
    feats = {k: jnp.asarray(rng.normal(size=(bgs.num_nodes, WIDTH)),
                            jnp.float32) for k in ("k", "q", "v")}
    r = bgs.num_etypes
    p = {"W_att": jnp.asarray(rng.normal(size=(r, heads, dk, dk)) / 3,
                              jnp.float32),
         "W_msg": jnp.asarray(rng.normal(size=(r, heads, dk, dk)) / 3,
                              jnp.float32),
         "w_prior": jnp.asarray(rng.normal(size=(r, heads)), jnp.float32)}
    kw = dict(backend=backend, tile=32, node_block=32)
    with jax.default_matmul_precision("highest"):
        multi = HectorModule(attention(WIDTH, WIDTH, heads), bgs, **kw)
        out = multi.apply(p, feats)["agg"]
        one = HectorModule(attention(dk, dk, 1), bgs, **kw)
        parts = [one.apply({k: v[:, h:h + 1] for k, v in p.items()},
                           {k: v[:, h * dk:(h + 1) * dk]
                            for k, v in feats.items()})["agg"]
                 for h in range(heads)]
    assert out.shape == (bgs.num_nodes, WIDTH)
    assert _rel(out, jnp.concatenate(parts, axis=1)) < 1e-6


def test_tiny_cell_is_correct(tmp_path):
    """The ``hgt-bgs.train_full`` cell, shrunk for the CPU (``bench_tiny``;
    width 32, so 8 heads of 4), runs ``correct`` through the harness."""
    c = tiny.cell("hgt-bgs.train_full", tmp_path)
    c.config["model"].update(dim=32, hidden=32)
    out = tiny.run(c)
    assert tiny.correct(out), out.checks
    assert out.attempted > 0 and out.failed == 0


# 3 nodes of one type, 2 relations: (src, dst, etype); U = 3 unique
# (src, relation) pairs, 2 distinct destinations
EDGES = np.array([(0, 1, 0), (0, 2, 0), (1, 2, 1), (0, 1, 1)], np.int32)


def test_hgt_published_step_by_hand():
    stats = work.graph_stats(EDGES[:, 0], EDGES[:, 1], EDGES[:, 2], 3)
    w = counts.step(stats, [4, 4, 2], {"num_etypes": 2, "num_ntypes": 1},
                    train=True, heads=2)
    # layer 0 (4 -> 4, dk 2): K, Q, V 2*3*N*d*d = 288, katt and msg
    # 2*2*U*H*dk^2 = 96, A 2*N*d*n = 96: forward 480; backward dW 480 and
    # dX 96 + 96 (none for K, Q, V on the features). Layer 1 (4 -> 2):
    # forward 288 + 96 + 48 = 432; backward 432 + 288 + 96 + 48
    assert w["segment_mm"]["flops"] == 480 + 672 + 432 + 864
    # layer 0 forward 4*(x 12 + weights 96 + k, q, v 36 + katt, msg 24 +
    # gelu(agg) 12 + o 12), backward 4*(dY 36 + 24 + 12 + dW 96 + dk, dv
    # 24 + d gelu(agg) 12); layer 1 the same with weights 88, o 6, dY 6 and
    # dX of the features 12
    assert w["segment_mm"]["bytes"] == 4 * (192 + 204 + 178 + 202)
    # per layer: softmax 3*E*H = 24 and sum 2*E*d = 32; 4*(E*H 8 + U*d 12 +
    # Nd*d 8) bytes
    assert w["traversal"] == {"flops": 2 * 56, "bytes": 2 * 112}
    # layer 0: forward 480 + scores 2*E*d + 2*E*H = 48 + 56 + skip 3*N*d
    # = 36; backward 480 + 192 + edges 8*E*d + 6*E*H = 176 + skip 72.
    # Layer 1: 432 + 48 + 56, then 432 + 432 + 176. Loss 3*5*N*c = 90
    assert w["model_flops"] == 620 + 920 + 536 + 1040 + 90
