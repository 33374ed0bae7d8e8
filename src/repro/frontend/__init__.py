"""Hector authoring frontend: the Python-embedded DSL + the unified
``hector.compile()`` entry point.

    import hector                      # (or: from repro import frontend as hector)

    @hector.model
    def rgat(g, e, n, in_dim, out_dim, slope=0.01):
        ...

    compiled = hector.compile(rgat, graph, layers=2, sample=5)
    params = compiled.init(0)
    logits = compiled.apply(params, feats)            # full graph
    logits = compiled.apply_blocks(params, mb, feats) # sampled mini-batch
    state, metrics = compiled.train_step(state, mb, labels, feats)

Models trace to the existing ``ir.inter_op.Program`` (no new IR) and are
validated at trace time with source-located diagnostics
(``ProgramValidationError``).
"""
from repro.core.ir.validate import (  # noqa: F401
    ProgramValidationError,
    check_var_refs,
    validate_program,
)
from repro.frontend.compile import CompiledRGNN, compile  # noqa: F401,A004
from repro.frontend.trace import (  # noqa: F401
    ModelSpec,
    aggregate,
    concat,
    dot,
    edge_softmax,
    exp,
    gelu,
    leaky_relu,
    model,
    neg,
    relu,
    sigmoid,
    tanh,
    unary,
)

__all__ = [
    "model", "compile", "CompiledRGNN", "ModelSpec",
    "ProgramValidationError", "validate_program", "check_var_refs",
    "aggregate", "concat", "dot", "edge_softmax", "unary",
    "relu", "leaky_relu", "sigmoid", "tanh", "exp", "neg", "gelu",
]
