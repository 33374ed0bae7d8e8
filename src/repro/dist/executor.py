"""Compiled multi-shard executors: ``shard_map`` over a data-only mesh.

One jitted callable per shape bucket runs *every* shard's block forward
(and, for training, backward + optimizer update) with all cross-shard
communication inside the compiled step:

* **halo features** — each device holds its shards' resident feature slabs
  ``[L, n_own, d]`` (``L = P / dp`` logical shards per device); the step
  opens with one ``all_gather`` over the data axis, giving every device the
  full ``[P, n_own, d]`` table from which each shard gathers its hop-0
  input rows (owned + halo) by host-precomputed ``(owner, row)`` indices.

* **gradient all-reduce** — each device computes its shards' *partial*
  losses ``sum(nll * mask) / B_total`` (linearity: the partials sum to the
  global mean loss exactly) and ``lax.map``s ``jax.grad`` over them,
  producing **stacked** per-shard gradients. Those are ``all_gather``-ed to
  ``[P, ...]`` in shard order and summed over the shard axis. This is the
  determinism-safe spelling of ``psum``: the gathered operands and the
  reduction tree depend only on ``P`` — not on how the shards distribute
  over devices — so dp=1 and dp=4 produce **bit-identical** gradients.

* **request-order outputs** — per-slot nll/logits are gathered to
  ``[P * b_max, ...]`` and un-permuted by the batcher's ``route`` index, so
  the reported loss is ``mean(nll[route])``: the same values, in the same
  order, reduced by the same HLO as the single-box step.

Everything is replicated except the stacked shard-axis arrays, so the
callable needs zero per-step host synchronization; the optimizer state is
donated exactly like ``BlockTrainExecutor``.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro.compat import shard_map
from repro.core import codegen
from repro.core.executor import _CachedExecutor


def _mesh_key(mesh) -> tuple:
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def _num_local(mesh, num_shards: int) -> int:
    """Logical shards per device (elastic folding): ``L = P / dp``."""
    dp = int(np.prod(mesh.devices.shape))
    if num_shards % dp:
        raise ValueError(
            f"{num_shards} shards cannot fold onto {dp} devices "
            f"(need num_shards % dp == 0)")
    return num_shards // dp


class _ShardedExecutor(_CachedExecutor):
    """Shared plumbing: plans + data mesh + the per-shard forward."""

    def __init__(self, plans: Sequence, mesh, backend: str = "xla",
                 activation: str = "relu", donate_argnums: Sequence[int] = (),
                 decisions=None, tag: str = ""):
        super().__init__(donate_argnums=donate_argnums, decisions=decisions,
                         static_key=(tag, _mesh_key(mesh))
                         + tuple(p.fingerprint() for p in plans))
        self.plans = list(plans)
        self.mesh = mesh
        self.backend = backend
        self.activation = activation

    def _place(self, replicated, sharded):
        """Put a step's operands on the data mesh — the stacked shard-axis
        operands split over ``"data"``, the rest replicated — instead of
        leaving them on the device that built them. Operands already
        placed so are not moved."""
        return (jax.device_put(replicated, NamedSharding(self.mesh, PS())),
                jax.device_put(sharded,
                               NamedSharding(self.mesh, PS("data"))))

    def _forward_one(self, params, full_feats, shard):
        """One shard's block forward from the gathered feature table."""
        gts, kls, dstl, perm, orow, lrow = shard
        x = full_feats[orow, lrow]
        return codegen.execute_block_sequence(
            self.plans, params, gts, kls, dstl, perm, {"feature": x},
            backend=self.backend, activation=self.activation,
            decisions=self.decisions)


class ShardedServeExecutor(_ShardedExecutor):
    """Compiled multi-shard inference: returns ``[B, C]`` seed logits in
    request order. Feature slabs are persistent (never donated)."""

    def __init__(self, plans: Sequence, mesh, backend: str = "xla",
                 activation: str = "relu", decisions=None):
        super().__init__(plans, mesh, backend, activation,
                         decisions=decisions, tag="serve")

    def hector_sharded_blocks(self, params, own_feats, gts, kls, dstl, perm,
                              orow, lrow, route):
        self._count_trace()

        def body(params, own_feats, gts, kls, dstl, perm, orow, lrow):
            full_feats = lax.all_gather(own_feats, "data", axis=0,
                                        tiled=True)
            logits_l = lax.map(
                lambda sh: self._forward_one(params, full_feats, sh),
                (gts, kls, dstl, perm, orow, lrow))
            return lax.all_gather(logits_l, "data", axis=0, tiled=True)

        d, r = PS("data"), PS()
        logits = shard_map(
            body, mesh=self.mesh,
            in_specs=(r, d, d, d, d, d, d, d), out_specs=r,
            check_vma=False,
        )(params, own_feats, gts, kls, dstl, perm, orow, lrow)
        num_parts, b_max = logits.shape[0], logits.shape[1]
        return logits.reshape(num_parts * b_max, -1)[route]

    def run_minibatch(self, params, smb, own_feats) -> jnp.ndarray:
        """Logits for ``smb.seeds`` (request order) from the per-owner
        feature slabs ``own_feats [P, n_own, d]``."""
        _num_local(self.mesh, smb.num_shards)
        (params, route), sharded = self._place(
            (params, smb.route),
            (own_feats, list(smb.tensors), list(smb.layouts),
             list(smb.dst_locals), smb.seed_perm, smb.owner_rows,
             smb.local_rows))
        return self._call(self.hector_sharded_blocks, params, *sharded,
                          route)


class ShardedTrainExecutor(_ShardedExecutor):
    """Compiled multi-shard SGD step: per-shard partial backward, in-step
    gradient all-reduce (gather + ordered shard-axis sum), optimizer
    update, request-order loss/accuracy — one dispatch per step."""

    def __init__(self, plans: Sequence, opt, mesh, backend: str = "xla",
                 activation: str = "relu", decisions=None):
        super().__init__(plans, mesh, backend, activation,
                         donate_argnums=(0,), decisions=decisions,
                         tag="train")
        self.opt = opt

    def hector_sharded_train_step(self, state, own_feats, gts, kls, dstl,
                                  perm, orow, lrow, labels, mask, route,
                                  inv_b):
        self._count_trace()

        def body(params, own_feats, gts, kls, dstl, perm, orow, lrow,
                 labels, mask):
            full_feats = lax.all_gather(own_feats, "data", axis=0,
                                        tiled=True)

            def one(sh):
                gts, kls, dstl, perm, orow, lrow, labels, mask = sh

                def loss_fn(p):
                    logits = self._forward_one(
                        p, full_feats, (gts, kls, dstl, perm, orow, lrow))
                    with jax.named_scope("loss"):
                        logp = jax.nn.log_softmax(logits)
                        nll = -jnp.take_along_axis(
                            logp, labels[:, None], axis=1)[:, 0]
                        return jnp.sum(nll * mask) * inv_b, (nll, logits)

                (_, (nll, logits)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                return g, nll, logits

            g_l, nll_l, logits_l = lax.map(
                one, (gts, kls, dstl, perm, orow, lrow, labels, mask))
            # determinism-safe all-reduce: gather per-shard partials in
            # shard order, sum over the shard axis — the operands and the
            # reduction are identical for every device count
            g_all = lax.all_gather(g_l, "data", axis=0, tiled=True)
            grads = jax.tree_util.tree_map(
                lambda a: jnp.sum(a, axis=0), g_all)
            nll = lax.all_gather(nll_l, "data", axis=0, tiled=True)
            logits = lax.all_gather(logits_l, "data", axis=0, tiled=True)
            return grads, nll, logits

        d, r = PS("data"), PS()
        grads, nll, logits = shard_map(
            body, mesh=self.mesh,
            in_specs=(r, d, d, d, d, d, d, d, d, d),
            out_specs=(r, r, r), check_vma=False,
        )(state.params, own_feats, gts, kls, dstl, perm, orow, lrow,
          labels, mask)

        num_parts, b_max = nll.shape
        loss = jnp.mean(nll.reshape(num_parts * b_max)[route])
        logits_req = logits.reshape(num_parts * b_max, -1)[route]
        labels_req = labels.reshape(num_parts * b_max)[route]
        acc = jnp.mean((jnp.argmax(logits_req, axis=-1) == labels_req)
                       .astype(jnp.float32))
        new_state = self.opt.update(grads, state)
        return new_state, {"loss": loss, "accuracy": acc}

    def grad_and_update(self, state, smb, labels, own_feats):
        """One optimizer step over a ``ShardedMiniBatch``.

        ``labels`` is the *global* per-node label array (the batcher routed
        the seeds, so labels are sliced per shard here); ``own_feats`` is
        the persistent ``[P, n_own, d]`` feature slab stack. Returns
        ``(new_state, {"loss", "accuracy"})`` like the single-box step.
        """
        _num_local(self.mesh, smb.num_shards)
        inv_b = jnp.float32(1.0 / len(smb.seeds))
        (state, route, inv_b), sharded = self._place(
            (state, smb.route, inv_b),
            (own_feats, list(smb.tensors), list(smb.layouts),
             list(smb.dst_locals), smb.seed_perm, smb.owner_rows,
             smb.local_rows, smb.slice_labels(labels), smb.mask))
        return self._call(self.hector_sharded_train_step, state, *sharded,
                          route, inv_b)

    def lowered_hlo(self, state, smb, labels, own_feats) -> str:
        """Lowered (StableHLO) text of the whole train step for these
        arguments — lets the ``dist_smoke`` gate assert the halo-feature
        and gradient collectives live *inside* the one jitted module
        rather than as separate dispatches. Traces a throwaway instance of
        the step (bumping ``trace_count``); it never enters the compile
        cache."""
        _num_local(self.mesh, smb.num_shards)
        inv_b = jnp.float32(1.0 / len(smb.seeds))
        return jax.jit(self.hector_sharded_train_step).lower(
            state, own_feats, list(smb.tensors), list(smb.layouts),
            list(smb.dst_locals), smb.seed_perm, smb.owner_rows,
            smb.local_rows, smb.slice_labels(labels), smb.mask, smb.route,
            inv_b).as_text()
