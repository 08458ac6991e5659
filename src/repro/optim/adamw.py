"""AdamW with decoupled weight decay, global-norm clipping, LR schedules.

Self-contained (no optax): state is a plain pytree so the two-level
checkpoint manager serializes it unchanged, and ``init`` is traceable so
abstract (compile-only) state costs no memory.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

PyTree = Any


def cosine_warmup(
    peak_lr: float,
    warmup_steps: int = 500,
    total_steps: int = 100_000,
    final_frac: float = 0.1,
) -> Callable[[jax.Array], jax.Array]:
    def schedule(step: jax.Array) -> jax.Array:
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(warmup_steps, 1)
        t = jnp.clip((step - warmup_steps) / max(total_steps - warmup_steps, 1), 0.0, 1.0)
        cos = peak_lr * (final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
        return jnp.where(step < warmup_steps, warm, cos)

    return schedule


def global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def clip_by_global_norm(tree: PyTree, max_norm: float) -> tuple[PyTree, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), tree), norm


@dataclasses.dataclass(frozen=True)
class AdamW:
    learning_rate: float | Callable[[jax.Array], jax.Array] = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    max_grad_norm: float = 1.0

    def init(self, params: PyTree) -> dict:
        zeros = lambda p: jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        return {"m": zeros(params), "v": zeros(params), "count": jnp.zeros((), jnp.int32)}

    def _lr(self, count: jax.Array) -> jax.Array:
        if callable(self.learning_rate):
            return self.learning_rate(count)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def update(
        self, grads: PyTree, state: dict, params: PyTree
    ) -> tuple[PyTree, dict, dict]:
        """Returns (updates, new_state, metrics)."""
        if self.max_grad_norm > 0:
            grads, gnorm = clip_by_global_norm(grads, self.max_grad_norm)
        else:
            gnorm = global_norm(grads)
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        b1c = 1.0 - self.b1**cf
        b2c = 1.0 - self.b2**cf
        lr = self._lr(count)

        def upd(g, m, v, p):
            gf = g.astype(jnp.float32)
            m_new = self.b1 * m + (1 - self.b1) * gf
            v_new = self.b2 * v + (1 - self.b2) * gf * gf
            mhat = m_new / b1c
            vhat = v_new / b2c
            step = mhat / (jnp.sqrt(vhat) + self.eps)
            step = step + self.weight_decay * p.astype(jnp.float32)
            return (-lr * step).astype(p.dtype), m_new, v_new

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        flat_p = treedef.flatten_up_to(params)
        out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
        updates = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        new_state = {"m": new_m, "v": new_v, "count": count}
        metrics = {"grad_norm": gnorm, "lr": lr}
        return updates, new_state, metrics


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda p, u: p + u.astype(p.dtype), params, updates)
