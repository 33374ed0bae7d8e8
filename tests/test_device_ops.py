"""Device work named after the model: every instruction of a compiled
training step gets an owner (an IR op's scope, the loss or the optimizer)
and a direction from the program's own scopes (``obs/device_ops.py``), and
the executors name their programs for what they run."""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import codegen
from repro.core.graph import synthetic_heterograph
from repro.obs import device_ops
from repro.optim import AdamW
from repro.train import EngineConfig, FullGraphTrainer, RGNNEngine

_LINE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S*)\s.*?"
                   r"([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def graph():
    return synthetic_heterograph(num_nodes=120, num_edges=900, num_ntypes=4,
                                 num_etypes=7, seed=0)


_STEPS = {}


def _train_step_hlo(graph, model):
    """One step of a tiny full-graph trainer on the Pallas kernels
    (interpret mode); returns the step's optimized HLO text and the engine
    (compiled once per model)."""
    if model not in _STEPS:
        _STEPS[model] = _compile_train_step(graph, model)
    return _STEPS[model]


def _compile_train_step(graph, model):
    eng = RGNNEngine(graph, EngineConfig(
        model=model, layers=2, dim=16, hidden=12, classes=6, fanouts=[3, 3],
        backend="pallas_interpret", tile=8, node_block=8, seed=0))
    rng = np.random.default_rng(1)
    feats = jnp.asarray(rng.normal(size=(graph.num_nodes, 16)), jnp.float32)
    labels = rng.integers(0, 6, graph.num_nodes)
    tr = FullGraphTrainer(eng, feats, labels, np.arange(graph.num_nodes),
                          opt=AdamW(learning_rate=1e-2), log=None)
    tr.step(tr.init_state(eng.init_params(jax.random.key(0))))
    (_, compiled), = tr.step_exec._cache.values()
    return compiled.as_text(), eng


def _instructions(text):
    """(name, result type, opcode, owner parsed from its own metadata) of
    every instruction, fused ones included."""
    out = []
    for line in text.splitlines():
        m = _LINE.match(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), m.group(3),
                        device_ops.parse_op_name(op.group(1)) if op
                        else None))
    return out


@pytest.mark.parametrize("model", ["rgat", "rgcn"])
def test_train_step_instructions_have_owners(graph, model):
    text, eng = _train_step_hlo(graph, model)
    unique_pairs = int(eng.gt.unique_src.shape[0])   # compact message rows
    module, table = device_ops.parse_hlo(text)
    assert module == "jit_hector_train_step"
    assert device_ops.table(module) == table      # recorded at compile
    instrs = _instructions(text)
    for name, _, opcode, own in instrs:
        if opcode in ("dot", "scatter", "custom-call"):
            assert own is not None, (name, opcode)
            assert device_ops.OWNERS.match(own.owner), own
    owners = {o.owner for o in table.values() if o is not None}
    assert {"loss", "optimizer"} <= owners
    for prefix in ("l0.gemm.", "l1.gemm.", "l1.traversal."):
        assert any(o.startswith(prefix) for o in owners), prefix
    # the gradient scatter of each layer's compact messages, written in the
    # gather-fused traversal kernel's backward (``kernels/ops.py``,
    # ``dmsg = zeros_like(msg).at[msg_rows].add``): a [unique pairs, width]
    # scatter-add owned by that layer's traversal op, backward
    for layer, width in ((0, 12), (1, 6)):
        want = f"f32[{unique_pairs},{width}]"
        owned = {(o.owner.split(".")[:2] == [f"l{layer}", "traversal"],
                  o.direction, o.inner.split("/")[-1])
                 for _, rtype, opcode, o in instrs
                 if opcode == "scatter" and rtype.startswith(want)}
        assert owned == {(True, "backward", "scatter-add")}, (layer, owned)


def _rows(rtype):
    return int(rtype.split("[")[1].split(",")[0].split("]")[0])


@pytest.mark.parametrize("model", ["rgat", "rgcn"])
def test_padding_transposes_are_owned_gemm_gathers(graph, model):
    """The backward of the GEMM's tile padding (``ops.pad_rows`` and
    ``ops.unpad_rows``) moves rows with gathers into the padded rows, owned
    by that GEMM op, backward; no scatter writes padded rows, and no gather
    or scatter is left without an owner."""
    text, eng = _train_step_hlo(graph, model)
    lays = eng.layouts
    segs = (lays.edge_seg, lays.unique_seg, lays.node_seg)
    padded = {int(s.row_map.shape[0]) for s in segs}
    compact = {int(s.inv_map.shape[0]) for s in segs}
    instrs = [(rtype, opcode, o) for _, rtype, opcode, o in
              _instructions(text) if opcode in ("gather", "scatter")]
    assert all(o is not None for _, _, o in instrs)
    assert not [(rtype, o) for rtype, opcode, o in instrs
                if opcode == "scatter" and _rows(rtype) in padded]
    # the GEMM ops that un-pad their rows (a dense GEMM such as RGCN's
    # ``h_self`` has no padding)
    unpadding = {o.owner for rtype, opcode, o in instrs
                 if opcode == "gather" and _rows(rtype) in compact
                 and o.direction == "forward" and ".gemm." in o.owner}
    assert unpadding
    backward = {o.owner for rtype, opcode, o in instrs
                if opcode == "gather" and _rows(rtype) in padded
                and o.direction == "backward"}
    assert backward == unpadding


@pytest.mark.parametrize("model", ["rgat", "hgt_published"])
def test_message_gather_backward_is_owned_row_sum(graph, monkeypatch, model):
    """With gather fusion off, as on the cells' full graphs, the transpose
    of the compact message gather ``msg[msg_rows]`` sums by message row on
    the traversal template (``ops.gather_msg_rows``), owned by each layer's
    traversal op, backward. No scatter writes the ``[Em, d]`` message rows
    (RGAT) or the head-folded ``[H*Em, dh]`` rows (HGT, 4 heads); HGT keeps
    one ``[Em, H*dh]`` scatter a layer, the transpose of its score gather
    ``katt[edge_to_unique]``."""
    import functools
    from repro.models import hgt_published_program
    heads = 4 if model == "hgt_published" else 1
    monkeypatch.setattr(codegen, "_gather_fits", lambda *a, **k: False)
    text, eng = _compile_train_step(
        graph, functools.partial(hgt_published_program, heads=heads)
        if heads > 1 else model)
    em = int(eng.gt.unique_src.shape[0])
    instrs = _instructions(text)
    # feature-wide scatters (RGAT's one-column score gather ``atts`` keeps
    # its own 1-D scatter into the compact rows)
    scatters = [_rows(rtype) for _, rtype, opcode, _ in instrs
                if opcode == "scatter" and "," in rtype.split("]")[0]]
    assert heads * em not in scatters or heads == 1
    assert scatters.count(em) == (2 if heads > 1 else 0)
    owners = {(o.owner.split(".")[0], o.owner.split(".")[1], o.direction)
              for _, _, _, o in instrs
              if o is not None and "seg_row_sum_padded" in o.inner}
    assert owners == {("l0", "traversal", "backward"),
                      ("l1", "traversal", "backward")}


def test_scope_names_never_match_a_kernel_name(graph):
    """The roofline readers match kernel names against instruction names;
    no op scope may carry one."""
    import json
    import pathlib
    names = json.loads((pathlib.Path(__file__).parents[1] / "bench" /
                        "metrics" / "kernel_names.json").read_text())
    patterns = [re.compile(p) for ps in names.values() for p in ps]
    for model in ("rgat", "rgcn", "hgt", "rgcn_cat", "hgt_published"):
        eng = RGNNEngine(graph, EngineConfig(model=model, layers=2, dim=8,
                                             hidden=8, classes=4))
        for i, plan in enumerate(eng.plans):
            scopes = [codegen.op_scope(op, i) for op in plan.ops]
            scopes.append(codegen.output_scope(plan, i))
            for s in scopes:
                assert device_ops.OWNERS.match(s), s
                assert not any(p.search(s) for p in patterns), s


def test_hgt_published_owner_kinds(graph):
    """HGT's node-typed GEMMs (K, Q, V, A-Linear) own their device work
    under the scope kind ``nodegemm``, its per-head relation GEMMs under
    ``gemm``, the edge scores, softmax and aggregation under
    ``traversal`` and the GELU under ``nodewise`` (its derivative fuses
    into the A-Linear's backward)."""
    import functools
    from repro.models import hgt_published_program
    text, _ = _compile_train_step(
        graph, functools.partial(hgt_published_program, heads=4))
    _, table = device_ops.parse_hlo(text)
    kinds = {(o.owner.split(".")[1], o.direction) for o in table.values()
             if o is not None and re.match(r"l\d+\.", o.owner)}
    for kind in ("nodegemm", "gemm", "traversal"):
        assert {(kind, "forward"), (kind, "backward")} <= kinds, kind
    assert ("nodewise", "forward") in kinds


def test_op_name_parsing():
    p = device_ops.parse_op_name
    assert p("jit(hector_train_step)/transpose(jvp(loss))/"
             "transpose(jvp(jit(take_along_axis)))/scatter-add") == \
        device_ops.Owner("loss", "backward", "take_along_axis/scatter-add")
    # a primitive named transpose is not the transpose transform
    assert p("jit(f)/jvp(l0.gemm.hs)/transpose").direction == "forward"
    assert p("jit(f)/optimizer/mul").owner == "optimizer"
    assert p("jit(f)/jvp(jit(seg_stats_padded))/while") is None
    assert device_ops.instruction_name(
        "%fusion.55 = f32[3231104,64]{0,1:T(8,128)}") == "fusion.55"
