"""Frontend DSL tests: traced programs are structurally identical to the
hand-built IR trees the models used to assemble, malformed models are
rejected at trace time with source-located diagnostics, and the unified
``hector.compile()`` facade drives every execution mode."""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import hector
from repro.core.graph import synthetic_heterograph
from repro.core.ir import inter_op as I
from repro.core.ir.passes import lower_program
from repro.models import (DSL_MODELS, baselines, hgt_program, rgat_program,
                          rgcn_cat_program, rgcn_program)

# §4.1: the paper expressed its three models in 51 lines of model code
PAPER_MODELS = ("rgcn", "rgat", "hgt")
MAX_MODEL_LOC = 60


# ---------------------------------------------------------------------------
# hand-built reference trees (the pre-DSL model definitions, verbatim)
# ---------------------------------------------------------------------------
def rgcn_handbuilt(in_dim, out_dim, activation="relu"):
    W_r = I.Weight("W_rel", (in_dim, out_dim), indexed_by="etype")
    W_0 = I.Weight("W_self", (in_dim, out_dim), indexed_by=None)
    stmts = [
        I.EdgeCompute("msg", I.TypedLinear(I.SrcFeature("feature"), W_r)),
        I.NodeAggregate("h_agg", msg="msg", reduce="mean"),
        I.NodeCompute("h_self", I.Linear(I.NodeFeature("feature"), W_0)),
        I.NodeCompute(
            "h_out",
            I.Unary(activation,
                    I.Binary("add", I.NodeVar("h_agg"), I.NodeVar("h_self")))),
    ]
    return I.Program(stmts=stmts, outputs=["h_out"], name="rgcn")


def rgat_handbuilt(in_dim, out_dim, slope=0.01):
    W = I.Weight("W_rel", (in_dim, out_dim), indexed_by="etype")
    w_s = I.Weight("w_att_src", (out_dim,), indexed_by="etype")
    w_t = I.Weight("w_att_dst", (out_dim,), indexed_by="etype")
    stmts = [
        I.EdgeCompute("hs", I.TypedLinear(I.SrcFeature("feature"), W)),
        I.EdgeCompute("atts", I.DotProduct(I.EdgeVar("hs"), w_s)),
        I.EdgeCompute(
            "attt",
            I.DotProduct(I.TypedLinear(I.DstFeature("feature"), W), w_t)),
        I.EdgeCompute(
            "att_raw",
            I.Unary("leaky_relu",
                    I.Binary("add", I.EdgeVar("atts"), I.EdgeVar("attt")),
                    alpha=slope)),
        I.EdgeSoftmax("att", "att_raw"),
        I.NodeAggregate("h_out", msg="hs", scale="att"),
    ]
    return I.Program(stmts=stmts, outputs=["h_out"], name="rgat")


def hgt_handbuilt(in_dim, out_dim):
    W_K = I.Weight("W_K", (in_dim, out_dim), indexed_by="ntype")
    W_Q = I.Weight("W_Q", (in_dim, out_dim), indexed_by="ntype")
    W_V = I.Weight("W_V", (in_dim, out_dim), indexed_by="ntype")
    W_A = I.Weight("W_att", (out_dim, out_dim), indexed_by="etype")
    W_M = I.Weight("W_msg", (out_dim, out_dim), indexed_by="etype")
    inv_sqrt_d = 1.0 / math.sqrt(out_dim)
    stmts = [
        I.NodeCompute("kk", I.TypedLinear(I.NodeFeature("feature"), W_K)),
        I.NodeCompute("qq", I.TypedLinear(I.NodeFeature("feature"), W_Q)),
        I.NodeCompute("vv", I.TypedLinear(I.NodeFeature("feature"), W_V)),
        I.EdgeCompute("katt", I.TypedLinear(I.SrcFeature("kk"), W_A)),
        I.EdgeCompute("msg", I.TypedLinear(I.SrcFeature("vv"), W_M)),
        I.EdgeCompute(
            "att_raw",
            I.Binary("mul",
                     I.DotProduct(I.EdgeVar("katt"), I.DstFeature("qq")),
                     I.Scalar(inv_sqrt_d))),
        I.EdgeSoftmax("att", "att_raw"),
        I.NodeAggregate("h_out", msg="msg", scale="att"),
    ]
    return I.Program(stmts=stmts, outputs=["h_out"], name="hgt")


PAIRS = [
    ("rgcn", rgcn_program, rgcn_handbuilt),
    ("rgat", rgat_program, rgat_handbuilt),
    ("hgt", hgt_program, hgt_handbuilt),
]


# ---------------------------------------------------------------------------
# trace fidelity: DSL == hand-built IR, program and plan level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,traced_fn,hand_fn", PAIRS)
def test_traced_program_identical_to_handbuilt(name, traced_fn, hand_fn):
    traced, hand = traced_fn(16, 24), hand_fn(16, 24)
    assert traced == hand                      # dataclass equality (source excluded)
    assert traced.fingerprint() == hand.fingerprint()
    assert traced.describe() == hand.describe()
    # the traced program carries authoring provenance, the hand-built not
    assert traced.source and all(
        loc.line > 0 for loc in traced.source.values())


@pytest.mark.parametrize("name,traced_fn,hand_fn", PAIRS)
@pytest.mark.parametrize("reorder", [False, True])
@pytest.mark.parametrize("compact", [False, True])
def test_traced_plan_identical_to_handbuilt(name, traced_fn, hand_fn,
                                            reorder, compact):
    pt = lower_program(traced_fn(16, 24), reorder=reorder, compact=compact)
    ph = lower_program(hand_fn(16, 24), reorder=reorder, compact=compact)
    assert pt.describe() == ph.describe()
    assert pt.fingerprint() == ph.fingerprint()


def test_model_spec_tracing_is_repeatable():
    a, b = rgat_program(8, 8), rgat_program(8, 8)
    assert a == b and a.fingerprint() == b.fingerprint()
    # hyperparameters flow into the trace
    c = rgat_program(8, 8, slope=0.3)
    assert c != a and c.fingerprint() != a.fingerprint()


def test_program_describe_golden():
    assert rgcn_program(8, 8).describe() == (
        "Program<rgcn>\n"
        "  for e: e[msg] = (e.src.feature @ W_rel[etype:8x8])\n"
        "  for n: n[h_agg] = mean_incoming(e[msg])\n"
        "  for n: n[h_self] = (n.feature @ W_self[shared:8x8])\n"
        "  for n: n[h_out] = relu((n[h_agg] + n[h_self]))\n"
        "  outputs: h_out"
    )
    assert rgat_program(8, 8).describe() == (
        "Program<rgat>\n"
        "  for e: e[hs] = (e.src.feature @ W_rel[etype:8x8])\n"
        "  for e: e[atts] = dot(e[hs], w_att_src[etype:8])\n"
        "  for e: e[attt] = dot((e.dst.feature @ W_rel[etype:8x8]), "
        "w_att_dst[etype:8])\n"
        "  for e: e[att_raw] = leaky_relu((e[atts] + e[attt]), 0.01)\n"
        "  for e: e[att] = edge_softmax(e[att_raw])\n"
        "  for n: n[h_out] = sum_incoming(e[hs] * e[att])\n"
        "  outputs: h_out"
    )


def test_fingerprint_ignores_source_but_not_structure():
    prog = rgcn_program(8, 8)
    stripped = prog.clone()
    stripped.source = None
    assert stripped == prog
    assert stripped.fingerprint() == prog.fingerprint()
    mutated = prog.clone()
    mutated.outputs = ["h_agg"]
    assert mutated != prog
    assert mutated.fingerprint() != prog.fingerprint()


# ---------------------------------------------------------------------------
# error paths: each diagnostic names the offending model line
# ---------------------------------------------------------------------------
def _trace_error(spec, *args, **kwargs) -> hector.ProgramValidationError:
    with pytest.raises(hector.ProgramValidationError) as ei:
        spec(*args, **kwargs)
    return ei.value


def test_error_undefined_edge_var():
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o), indexed_by="etype")
        e["hs"] = e.src["feature"] @ W
        e["att"] = hector.edge_softmax(e["scores"])
        n["h"] = hector.aggregate(e["hs"], scale=e["att"])
        return n["h"]

    err = _trace_error(m, 8, 8)
    msg = str(err)
    assert "undefined edge var 'scores'" in msg
    assert "test_frontend.py" in msg            # the offending model line
    assert 'hector.edge_softmax(e["scores"])' in msg


def test_error_wrong_weight_index():
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W_n", (i, o), indexed_by="ntype")
        e["hs"] = e.src["feature"] @ W
        n["h"] = hector.aggregate(e["hs"])
        return n["h"]

    msg = str(_trace_error(m, 8, 8))
    assert "W_n" in msg and "indexed_by='ntype'" in msg
    assert "for-each-edge" in msg
    assert "test_frontend.py" in msg and 'e.src["feature"] @ W' in msg


def test_error_dim_mismatch_in_matmul():
    @hector.model
    def m(g, e, n, i, o):
        W1 = g.weight("W1", (i, 32), indexed_by="etype")
        W2 = g.weight("W2", (16, o), indexed_by="etype")
        e["hs"] = (e.src["feature"] @ W1) @ W2
        n["h"] = hector.aggregate(e["hs"])
        return n["h"]

    msg = str(_trace_error(m, 8, 8))
    assert "dim mismatch in '@'" in msg
    assert "has dim 32" in msg and "'W2' expects 16" in msg
    assert "test_frontend.py" in msg


def test_error_node_var_where_edge_var_required():
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o))
        n["hn"] = n["feature"] @ W
        n["h"] = hector.aggregate(n["hn"])
        return n["h"]

    msg = str(_trace_error(m, 8, 8))
    assert "requires an edge var" in msg and "n[hn] is a node var" in msg
    assert "test_frontend.py" in msg and 'hector.aggregate(n["hn"])' in msg


def test_error_edge_softmax_on_node_var():
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o))
        n["hn"] = n["feature"] @ W
        e["att"] = hector.edge_softmax(n["hn"])
        return n["hn"]

    msg = str(_trace_error(m, 8, 8))
    assert "edge_softmax requires an edge var" in msg


def test_error_aggregate_assigned_to_edge_var():
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o), indexed_by="etype")
        e["hs"] = e.src["feature"] @ W
        e["h"] = hector.aggregate(e["hs"])
        return e["h"]

    msg = str(_trace_error(m, 8, 8))
    assert "reduces edges into nodes" in msg and "n['h']" in msg


def test_error_input_dim_conflict_between_uses():
    """The first '@' binds the input feature's dim; a later use with a
    differently-shaped weight is a located mismatch."""
    @hector.model
    def m(g, e, n, i, o):
        W1 = g.weight("W1", (i, o), indexed_by="etype")
        W2 = g.weight("W2", (i + 1, o), indexed_by="etype")
        e["a"] = e.src["feature"] @ W1
        e["b"] = e.src["feature"] @ W2
        n["h"] = hector.aggregate(e["a"])
        return n["h"]

    msg = str(_trace_error(m, 8, 8))
    assert "dim mismatch in '@'" in msg
    assert "has dim 8" in msg and "'W2' expects 9" in msg
    assert "statement 1" in msg


def test_error_typoed_node_var_read():
    """Reading a near-miss of a produced node var must fail at trace time
    with the defined names, not surface later as an executor fallback."""
    @hector.model
    def m(g, e, n, i, o):
        W_r = g.weight("W_rel", (i, o), indexed_by="etype")
        W_0 = g.weight("W_self", (i, o))
        e["msg"] = e.src["feature"] @ W_r
        n["h_agg"] = hector.aggregate(e["msg"], reduce="mean")
        n["h_self"] = n["feature"] @ W_0
        n["h_out"] = hector.relu(n["h_agg"] + n["h_sefl"])   # typo
        return n["h_out"]

    msg = str(_trace_error(m, 8, 8))
    assert "n.h_sefl" in msg and "check the name" in msg
    assert "h_agg" in msg and "h_self" in msg   # lists the defined vars
    assert "test_frontend.py" in msg


def test_fingerprint_distinguishes_close_scalars():
    """Scalar constants render at full precision: programs differing below
    1e-6 relative must not fingerprint identically."""
    def prog(c):
        W = I.Weight("W", (8, 8), indexed_by="etype")
        return I.Program(
            stmts=[I.EdgeCompute("hs",
                                 I.TypedLinear(I.SrcFeature("feature"), W)),
                   I.EdgeCompute("s", I.Binary("mul", I.EdgeVar("hs"),
                                               I.Scalar(c))),
                   I.NodeAggregate("h", msg="s")],
            outputs=["h"], name="p")

    a, b = prog(0.12345678), prog(0.12345679)
    assert a.fingerprint() != b.fingerprint()


def test_aggregate_materializes_distinct_temps_for_msg_and_scale():
    """Expression-valued msg AND scale must land in distinct derived edge
    vars (regression: both used to collapse onto one temp name)."""
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o), indexed_by="etype")
        w = g.weight("w", (o,), indexed_by="etype")
        e["hs"] = e.src["feature"] @ W
        n["h"] = hector.aggregate(e["hs"] * 2.0,
                                  scale=hector.exp(hector.dot(e["hs"], w)))
        return n["h"]

    prog = m(8, 8)
    agg = [s for s in prog.stmts if isinstance(s, I.NodeAggregate)][0]
    assert agg.msg != agg.scale
    defs = [s.out for s in prog.stmts if isinstance(s, I.EdgeCompute)]
    assert len(defs) == len(set(defs))          # no shadowed definitions


def test_reflected_scalar_division_traces():
    """1.0 / expr must trace to Binary('div', Scalar, expr), not raise a
    bare TypeError outside the DSL's diagnostics."""
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o), indexed_by="etype")
        w = g.weight("w", (o,), indexed_by="etype")
        e["hs"] = e.src["feature"] @ W
        e["s"] = 1.0 / hector.exp(hector.dot(e["hs"], w))
        n["h"] = hector.aggregate(e["hs"], scale=e["s"])
        return n["h"]

    prog = m(8, 8)
    div = [s for s in prog.stmts if isinstance(s, I.EdgeCompute)
           and s.out == "s"][0].expr
    assert isinstance(div, I.Binary) and div.op == "div"
    assert isinstance(div.a, I.Scalar) and div.a.value == 1.0


def test_scalar_broadcast_keeps_input_dim_unknown():
    """x * 2.0 must not collapse the dim to 1 and reject a later '@'
    (regression: scalar broadcasts inferred dim 1)."""
    @hector.model
    def m(g, e, n, i, o):
        W = g.weight("W", (i, o), indexed_by="etype")
        e["s"] = e.src["feature"] * 2.0
        e["hs"] = e["s"] @ W
        n["h"] = hector.aggregate(e["hs"])
        return n["h"]

    prog = m(8, 8)                              # traces without error
    assert prog.outputs == ["h"]


# ---------------------------------------------------------------------------
# the compile() facade
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def graph():
    return synthetic_heterograph(num_nodes=160, num_edges=1200, num_ntypes=4,
                                 num_etypes=7, seed=0)


@pytest.fixture(scope="module")
def feats(graph):
    rng = np.random.default_rng(1)
    return jnp.asarray(rng.normal(size=(graph.num_nodes, 16)), jnp.float32)


def test_compile_full_lifecycle(graph, feats):
    compiled = hector.compile("rgat", graph, layers=2, dim=16, hidden=16,
                              classes=8, sample=4, tile=8, node_block=8)
    params = compiled.init(0)
    out = compiled.apply(params, feats)
    assert out.shape == (graph.num_nodes, 8)
    assert bool(jnp.all(jnp.isfinite(out)))

    labels = np.random.default_rng(2).integers(0, 8, graph.num_nodes)
    loader = compiled.make_loader(
        lambda step: np.arange(24, dtype=np.int32), num_batches=3, depth=1)
    state = compiled.init_state(params)
    losses = []
    try:
        for mb in loader:
            logits = compiled.apply_blocks(params, mb, feats)
            assert logits.shape == (24, 8)
            state, metrics = compiled.train_step(
                state, mb, mb.seq.slice_labels(labels), feats)
            losses.append(float(metrics["loss"]))
    finally:
        loader.close()
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]              # the compiled step learns

    # init_state accepts every key flavor init() does (int / typed key /
    # legacy PRNGKey) and never mistakes a key for a params pytree
    for key in (0, jax.random.key(0), jax.random.PRNGKey(0)):
        st = compiled.init_state(key)
        assert isinstance(st.params, list) and isinstance(st.params[0], dict)


def test_compile_accepts_model_spec_and_kwargs(graph, feats):
    from repro.models import rgcn_cat
    compiled = hector.compile(rgcn_cat, graph, layers=1, dim=16, classes=8,
                              tile=8, node_block=8,
                              model_args={"activation": "tanh"})
    assert "rgcn_cat" in repr(compiled)
    params = compiled.init(0)
    out = compiled.apply(params, feats)
    assert out.shape == (graph.num_nodes, 8)
    # activation kwarg reached the traced program
    layer_prog = compiled.engine.stack.layers[0].program
    assert any(
        isinstance(s, I.NodeCompute) and isinstance(s.expr, I.Unary)
        and s.expr.op == "tanh" for s in layer_prog.stmts)


def test_compile_rejects_unknown_model(graph):
    with pytest.raises(ValueError, match="unknown model"):
        hector.compile("nope", graph)
    # the model-kwargs path must produce the same diagnostic, not KeyError
    with pytest.raises(ValueError, match="unknown model"):
        hector.compile("rgta", graph, slope=0.2)


def test_compile_matches_direct_module(graph, feats):
    """The facade's full-graph forward equals a hand-wired HectorModule."""
    from repro.core.module import HectorModule
    compiled = hector.compile("rgat", graph, layers=1, dim=16, classes=24,
                              tile=8, node_block=8)
    params = compiled.init(0)
    mod = HectorModule(rgat_program(16, 24), graph, tile=8, node_block=8)
    ref = mod.apply(params[0], {"feature": feats})["h_out"]
    np.testing.assert_allclose(compiled.apply(params, feats), ref,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the new DSL-authored model generalizes the surface
# ---------------------------------------------------------------------------
def test_rgcn_cat_lowers_without_fallback():
    plan = lower_program(rgcn_cat_program(16, 24))
    assert plan.fallback_count() == 0
    assert plan.gemm_count() == 3              # msg, h_self, h_mix
    assert plan.traversal_count() >= 2         # mean-agg + concat (+ act)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_rgcn_cat_matches_vanilla(graph, feats, backend):
    from repro.core.module import HectorModule
    mod = HectorModule(rgcn_cat_program(16, 24), graph, backend=backend,
                       tile=8, node_block=8)
    params = mod.init(jax.random.key(0))
    out = mod.apply(params, {"feature": feats})["h_out"]
    van = baselines.rgcn_cat_vanilla(params, graph.to_tensors(),
                                     {"feature": feats})["h_out"]
    assert out.shape == (graph.num_nodes, 24)
    np.testing.assert_allclose(out, van, rtol=2e-4, atol=2e-4)


def test_rgcn_cat_gradients_match(graph, feats):
    from repro.core.module import HectorModule
    mod = HectorModule(rgcn_cat_program(16, 24), graph,
                       backend="pallas_interpret", tile=8, node_block=8)
    params = mod.init(jax.random.key(0))
    g = jax.grad(lambda p: jnp.sum(
        mod.apply(p, {"feature": feats})["h_out"] ** 2))(params)
    gv = jax.grad(lambda p: jnp.sum(
        baselines.rgcn_cat_vanilla(p, graph.to_tensors(),
                                   {"feature": feats})["h_out"] ** 2))(params)
    for k in g:
        denom = float(jnp.max(jnp.abs(gv[k]))) + 1e-9
        np.testing.assert_allclose(np.asarray(g[k]) / denom,
                                   np.asarray(gv[k]) / denom,
                                   rtol=0, atol=5e-4, err_msg=k)


def test_rgcn_cat_registered_in_engine():
    from repro.train.engine import MODEL_PROGRAMS
    assert "rgcn_cat" in MODEL_PROGRAMS


# ---------------------------------------------------------------------------
# paper-scale brevity (§4.1): the three models stay within 60 LoC total
# ---------------------------------------------------------------------------
def test_three_model_definitions_within_60_loc():
    from repro.models import DSL_MODELS
    per_model = {k: DSL_MODELS[k].definition_loc
                 for k in ("rgcn", "rgat", "hgt")}
    assert sum(per_model.values()) <= 60, per_model


def test_loc_report_paper_parity():
    """§4.1: the DSL definitions of the paper's three models stay at
    paper-scale brevity (``ModelSpec.definition_loc``)."""
    per_model = {m: DSL_MODELS[m].definition_loc for m in PAPER_MODELS}
    assert sum(per_model.values()) <= MAX_MODEL_LOC, per_model
