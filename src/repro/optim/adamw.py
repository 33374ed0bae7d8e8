"""AdamW with decoupled weight decay, global-norm clipping, f32 moments.

No optax dependency: the update is a pure pytree transform so that the
ZeRO-1 sharding rules (launch/partitioning.py) apply to the moment tensors
directly and the whole optimizer steps inside one pjit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    mu: Any
    nu: Any
    step: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class AdamW:
    learning_rate: Callable[[jnp.ndarray], jnp.ndarray] | float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0

    def init(self, params) -> TrainState:
        """The state owns its buffers: the compiled train steps donate it,
        so it must not alias the caller's ``params``."""
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return TrainState(
            params=jax.tree.map(jnp.copy, params),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
            step=jnp.zeros((), jnp.int32),
        )

    def _lr(self, step):
        if callable(self.learning_rate):
            return self.learning_rate(step)
        return jnp.float32(self.learning_rate)

    @jax.named_scope("optimizer")
    def update(self, grads, state: TrainState) -> TrainState:
        step = state.step + 1
        if self.clip_norm is not None:
            gsq = jax.tree.reduce(
                lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
                grads, jnp.float32(0.0))
            gnorm = jnp.sqrt(gsq)
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)

        b1, b2 = self.b1, self.b2
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        lr = self._lr(step)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * g32 * g32
            mhat = m / bc1
            vhat = v / bc2
            delta = mhat / (jnp.sqrt(vhat) + self.eps)
            delta = delta + self.weight_decay * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - lr * delta
            return newp.astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(state.params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(state.mu)
        flat_v = tdef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        return TrainState(params=new_p, mu=new_m, nu=new_v, step=step)
