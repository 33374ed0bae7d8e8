"""Whole-plan compiled executors with an explicit compile cache.

The op-by-op ``codegen.execute_plan`` loop is the generated code's *meaning*;
running it from Python per batch leaves two costs on the serving hot path:
Python dispatch per op, and — without a stable jit entry point — a retrace
whenever shapes wobble. The executors here close a lowered plan (or a stack
of per-hop plans) over one traced function, jit it with the graph tensors,
kernel layouts, and features as **run-time pytree arguments**, and front it
with an explicit compile cache keyed by the argument signature (pytree
structure + leaf shapes/dtypes — i.e. the bucketed layout shapes).

Because sampled blocks are shape-bucketed (sampling/bucketing.py), the
signature set is small and steady-state serving reuses one compiled
executable per bucket: zero retraces, zero Python op dispatch. Cache hits,
misses, and actual traces are counted so tests and the serve_cached
benchmark can assert the steady state.

Each executor jits a function named for what it runs (``hector_forward``,
``hector_blocks``, ``hector_train_step``, ...), so a device profile names
the program ``jit_<name>``. A cache entry keeps the compiled executable
(``jit(...).lower(args).compile()``) and calls it; the compile records the
optimized HLO's instruction-to-owner table (``obs/device_ops.py``) from
that executable: no second trace, no second compile.

The training steps donate the optimizer state on every backend, the CPU
included: the new state has the old one's shapes, so the update runs in
place, and the old state is deleted by the call. Input features are not
donated: no output has their shape, so XLA could never reuse them.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import codegen
from repro.obs import device_ops


def signature(args) -> tuple:
    """Hashable compile-cache key: pytree structure + leaf shapes/dtypes.

    The treedef carries every static field (graph sizes, layout tile
    metadata), the leaves carry the bucketed array shapes — together exactly
    the information that determines the compiled executable.
    """
    return _signature(*jax.tree_util.tree_flatten(args))


def _signature(leaves, treedef) -> tuple:
    return treedef, tuple(
        (jnp.shape(l), jnp.result_type(l).name) for l in leaves)


class _CachedExecutor:
    """Shared machinery: explicit signature -> jitted-callable cache.

    ``decisions`` (a ``tune.TuningDecisions`` table, or None) is closed over
    by the traced function AND its fingerprint joins the cache key: swapping
    in a new table after (re)tuning can never reuse an executable compiled
    for the old variants.
    """

    def __init__(self, donate_argnums: Sequence[int] = (), decisions=None,
                 static_key: tuple = ()):
        self._cache: Dict[tuple, object] = {}
        self._donate_argnums = tuple(donate_argnums)
        # plan fingerprint(s): distinct lowered plans can never share a
        # compiled executable even if their argument signatures collide
        self._static_key = tuple(static_key)
        self.decisions = decisions
        self.cache_hits = 0
        self.cache_misses = 0
        self.trace_count = 0   # incremented inside the traced fn: counts
        #                        actual (re)traces, not cache bookkeeping

    def set_decisions(self, decisions) -> None:
        """Install a (new) tuning-decision table; subsequent calls compile
        fresh entries under its fingerprint."""
        self.decisions = decisions

    def _count_trace(self) -> None:
        """Called from inside the traced functions: counts actual
        (re)traces. Runs at trace time on the host — never inside the
        compiled executable — so the obs mirror adds no per-call cost."""
        self.trace_count += 1
        obs.metrics().counter("executor_traces",
                              executor=type(self).__name__).inc()

    def _call(self, program, *args):
        """Run ``program`` (the executor's one traced function) on
        ``args``. A cache entry holds its ``jit`` and, from the first call
        on concrete arrays, the executable compiled for their signature;
        a call under a transformation (``jax.grad`` around the executor)
        goes through the ``jit``."""
        fp = self.decisions.fingerprint() if self.decisions is not None \
            else None
        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = (self._static_key, fp) + _signature(leaves, treedef)
        entry = self._cache.get(key)
        if entry is None:
            self.cache_misses += 1
            obs.metrics().counter("executor_cache_misses",
                                  executor=type(self).__name__).inc()
            entry = self._cache[key] = [
                jax.jit(program, donate_argnums=self._donate_argnums), None]
        else:
            self.cache_hits += 1
            obs.metrics().counter("executor_cache_hits",
                                  executor=type(self).__name__).inc()
        if any(isinstance(l, jax.core.Tracer) for l in leaves):
            return entry[0](*args)
        if entry[1] is None:
            entry[1] = entry[0].lower(*args).compile()
            device_ops.record(entry[1])
        return entry[1](*args)

    @property
    def num_compiled(self) -> int:
        return len(self._cache)

    def cache_stats(self) -> Dict[str, int]:
        return {
            "compile_cache_hits": self.cache_hits,
            "compile_cache_misses": self.cache_misses,
            "trace_count": self.trace_count,
            "num_compiled": self.num_compiled,
        }


class PlanExecutor(_CachedExecutor):
    """Compiled full-graph forward for one lowered plan.

    ``gt``/``kl`` are arguments (not closure state), so one executor serves
    any graph whose signature matches — and distinct graphs simply occupy
    distinct cache entries.
    """

    def __init__(self, plan, backend: str = "xla", decisions=None,
                 layer: int = 0):
        super().__init__(decisions=decisions,
                         static_key=(plan.fingerprint(),))
        self.plan = plan
        self.backend = backend
        self.layer = layer         # the plan's place in its stack (scopes)

    def hector_forward(self, params, gt, kl, feats):
        self._count_trace()
        return codegen.execute_plan(self.plan, params, gt, feats, kl,
                                    self.backend, self.decisions, self.layer)

    def __call__(self, params, gt, kl, feats) -> Dict[str, jnp.ndarray]:
        return self._call(self.hector_forward, params, gt, kl, feats)


class BlockExecutor(_CachedExecutor):
    """Compiled sampled-minibatch forward for a stack of per-hop plans.

    One jitted callable covers the *entire* block sequence — every hop's
    GEMM/traversal kernels, inter-hop frontier narrowing, activations, and
    the final seed gather — so steady-state serving is a single compiled
    dispatch per batch.
    """

    def __init__(self, plans: Sequence, backend: str = "xla",
                 activation: str = "relu", decisions=None):
        super().__init__(decisions=decisions,
                         static_key=tuple(p.fingerprint() for p in plans))
        self.plans = list(plans)
        self.backend = backend
        self.activation = activation

    def hector_blocks(self, params, gts, kls, dst_locals, seed_perm, feats):
        self._count_trace()
        return codegen.execute_block_sequence(
            self.plans, params, gts, kls, dst_locals, seed_perm, feats,
            backend=self.backend, activation=self.activation,
            decisions=self.decisions)

    def __call__(self, params: Sequence[Dict[str, jnp.ndarray]],
                 gts: List, kls: List, dst_locals: List,
                 seed_perm, feats: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        return self._call(self.hector_blocks, list(params), list(gts),
                          list(kls), list(dst_locals), seed_perm, feats)

    def run_minibatch(self, params, mb, global_feats=None, *,
                      feats=None) -> jnp.ndarray:
        """Convenience entry over a ``sampling.MiniBatch``.

        Input-feature precedence: an explicit ``feats`` pytree, then the
        loader-attached ``mb.feats`` (pre-gathered by a tiered feature
        store inside the prefetch overlap), then an on-device gather from
        ``global_feats``."""
        if feats is None:
            feats = getattr(mb, "feats", None)
        if feats is None:
            feats = {"feature": global_feats[mb.input_ids]}
        return self(params, mb.tensors, mb.layouts, mb.dst_locals,
                    mb.seed_perm, feats)


# ---------------------------------------------------------------------------
# compiled training steps
# ---------------------------------------------------------------------------
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray):
    """Mean cross-entropy + accuracy over [rows, classes] logits and int
    labels; the per-seed training objective (one row per seed/node)."""
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    acc = jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                   .astype(jnp.float32))
    return jnp.mean(nll), acc


class BlockTrainExecutor(_CachedExecutor):
    """Compiled neighbor-sampled SGD step over a stack of per-hop plans.

    One jitted callable covers the whole step: block-sequence forward (every
    hop's kernels), per-seed cross-entropy on the gathered seed rows,
    backward through the gather-fused ``custom_vjp`` kernels, and the
    optimizer update — behind the same signature compile cache as the
    forward executors, so shape-bucketed mini-batches retrace zero times
    after warmup.

    The optimizer state is donated (its buffers are consumed by the update —
    callers must not reuse the old state).
    """

    def __init__(self, plans: Sequence, opt, backend: str = "xla",
                 activation: str = "relu", decisions=None):
        super().__init__(donate_argnums=(0,), decisions=decisions,
                         static_key=tuple(p.fingerprint() for p in plans))
        self.plans = list(plans)
        self.opt = opt
        self.backend = backend
        self.activation = activation

    def hector_block_train_step(self, state, gts, kls, dst_locals,
                                seed_perm, labels, feats):
        self._count_trace()

        def loss_fn(params):
            logits = codegen.execute_block_sequence(
                self.plans, params, gts, kls, dst_locals, seed_perm, feats,
                backend=self.backend, activation=self.activation,
                decisions=self.decisions)
            with jax.named_scope("loss"):
                return softmax_xent(logits, labels)

        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        new_state = self.opt.update(grads, state)
        return new_state, {"loss": loss, "accuracy": acc}

    def grad_and_update(self, state, mb, labels, feats):
        """One optimizer step over a ``sampling.MiniBatch``-shaped bundle.

        ``labels`` must be aligned with the requested seed order (use
        ``BlockSequence.slice_labels``); ``feats`` is the per-batch gathered
        feature dict for the first block's node set. Returns
        ``(new_state, {"loss", "accuracy"})``.
        """
        return self._call(self.hector_block_train_step, state,
                          list(mb.tensors), list(mb.layouts),
                          list(mb.dst_locals), mb.seed_perm, labels, feats)


class StackTrainExecutor(_CachedExecutor):
    """Compiled full-graph SGD step over a multi-layer stack — the training
    analogue of ``PlanExecutor``: layer-by-layer forward over the shared
    graph tensors/layouts, cross-entropy on the ``idx`` node rows, backward
    and optimizer update in one jitted callable.

    Serves as the parity baseline for the sampled trainer (full-fanout
    sampled steps must reproduce its loss and gradients) and as the
    periodic full-graph evaluator.
    """

    def __init__(self, plans: Sequence, opt, backend: str = "xla",
                 activation: str = "relu", decisions=None):
        super().__init__(donate_argnums=(0,), decisions=decisions,
                         static_key=tuple(p.fingerprint() for p in plans))
        self.plans = list(plans)
        self.opt = opt
        self.backend = backend
        self.activation = activation
        self._eval_fn = None

    def _forward(self, params, gt, kl, feats):
        act = codegen._ACTIVATIONS[self.activation]
        cur = dict(feats)
        h = None
        last = len(self.plans) - 1
        for i, (plan, p) in enumerate(zip(self.plans, params)):
            out = codegen.execute_plan(plan, p, gt, cur, kl, self.backend,
                                       self.decisions, i)
            with jax.named_scope(codegen.output_scope(plan, i)):
                h = out[plan.outputs[0]]
                if i < last:
                    cur = {"feature": act(h)}
        return h

    def hector_train_step(self, state, gt, kl, idx, labels, feats):
        self._count_trace()

        def loss_fn(params):
            h = self._forward(params, gt, kl, feats)
            with jax.named_scope("loss"):
                return softmax_xent(h[idx], labels)

        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        new_state = self.opt.update(grads, state)
        return new_state, {"loss": loss, "accuracy": acc}

    def grad_and_update(self, state, gt, kl, idx, labels, feats):
        """One full-graph optimizer step; loss is taken over the ``idx``
        node rows (the training split)."""
        return self._call(self.hector_train_step, state, gt, kl, idx, labels,
                          feats)

    def set_decisions(self, decisions) -> None:
        super().set_decisions(decisions)
        self._eval_fn = None   # compiled under the old decision table

    # -- compiled evaluation (no update) ---------------------------------
    def hector_eval(self, params, gt, kl, idx, labels, feats):
        h = self._forward(params, gt, kl, feats)
        with jax.named_scope("loss"):
            return softmax_xent(h[idx], labels)

    def evaluate(self, params, gt, kl, idx, labels, feats):
        """Full-graph loss/accuracy on the ``idx`` rows (jitted once —
        full-graph shapes are static)."""
        if self._eval_fn is None:
            self._eval_fn = jax.jit(self.hector_eval)
        loss, acc = self._eval_fn(params, gt, kl, idx, labels, feats)
        return {"loss": loss, "accuracy": acc}
