"""Optimizers from scratch (no optax in this environment).

Functional API mirroring optax: ``init(params) -> state``,
``update(grads, state, params) -> (updates, state)``; apply with
``apply_updates``.  All states are pytrees so they pjit/shard like params.

FedProx support: `proximal_grad` adds mu * (w - w_global) to the gradient,
which is the gradient of the paper's proximal term mu/2 ||w - w_global||^2.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


class AdamHyper(NamedTuple):
    learning_rate: float
    b1: float
    b2: float
    eps: float
    weight_decay: float


class Optimizer(NamedTuple):
    init: Callable[[Pytree], Pytree]
    update: Callable[[Pytree, Pytree, Pytree], Tuple[Pytree, Pytree]]
    # Adam's hyperparameters, for code that applies its step to a leaf
    # itself (kernels/dense_adam.py); None for every other optimizer
    adam: Optional[AdamHyper] = None


def apply_updates(params: Pytree, updates: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)), params, updates)


def zeros_like_f32(params: Pytree) -> Pytree:
    """fp32 moment buffers shaped like `params` (mixed-precision training
    and the server-side merge pipeline keep fp32 optimizer state even
    when the params themselves are lower precision)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, dtype=jnp.float32), params)


_zeros_like_f32 = zeros_like_f32


# --------------------------------------------------------------------------
def sgd(learning_rate: float, momentum: float = 0.0) -> Optimizer:
    """SGD, optionally with heavy-ball momentum. State: (count, velocity?)."""

    def init(params):
        if momentum == 0.0:
            return {"count": jnp.zeros((), jnp.int32)}
        return {"count": jnp.zeros((), jnp.int32),
                "velocity": _zeros_like_f32(params)}

    def update(grads, state, params=None):
        del params
        if momentum == 0.0:
            updates = jax.tree_util.tree_map(
                lambda g: -learning_rate * g.astype(jnp.float32), grads)
            return updates, {"count": state["count"] + 1}
        vel = jax.tree_util.tree_map(
            lambda v, g: momentum * v + g.astype(jnp.float32),
            state["velocity"], grads)
        updates = jax.tree_util.tree_map(lambda v: -learning_rate * v, vel)
        return updates, {"count": state["count"] + 1, "velocity": vel}

    return Optimizer(init, update)


# --------------------------------------------------------------------------
def adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, weight_decay: float = 0.0) -> Optimizer:
    """Adam / AdamW (decoupled weight decay when weight_decay > 0).

    m/v accumulators are fp32 regardless of param dtype (mixed-precision
    training keeps bf16 params with fp32 optimizer state).
    """

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "m": _zeros_like_f32(params),
                "v": _zeros_like_f32(params)}

    def update(grads, state, params):
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        m = jax.tree_util.tree_map(
            lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
            state["m"], grads)
        v = jax.tree_util.tree_map(
            lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state["v"], grads)
        bc1, bc2 = bias_corrections(cf, b1, b2)

        def step(m_, v_, p):
            upd = -learning_rate * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if weight_decay:
                upd = upd - learning_rate * weight_decay * p.astype(jnp.float32)
            return upd

        updates = jax.tree_util.tree_map(step, m, v, params)
        return updates, {"count": count, "m": m, "v": v}

    return Optimizer(init, update,
                     AdamHyper(learning_rate, b1, b2, eps, weight_decay))


def bias_corrections(t, b1: float, b2: float):
    """Adam's (1 − b1^t, 1 − b2^t) at step ``t`` (float32, from 1)."""
    return 1 - b1 ** t, 1 - b2 ** t


def adamw(learning_rate: float, weight_decay: float = 0.01, **kw) -> Optimizer:
    return adam(learning_rate, weight_decay=weight_decay, **kw)


OPTIMIZERS = {"sgd": sgd, "adam": adam, "adamw": adamw}


def make_optimizer(name: str, learning_rate: float, **kw) -> Optimizer:
    try:
        return OPTIMIZERS[name](learning_rate, **kw)
    except KeyError:
        raise ValueError(f"unknown optimizer {name!r}") from None


# --------------------------------------------------------------------------
def proximal_grad(grads: Pytree, params: Pytree, global_params: Pytree,
                  mu: float) -> Pytree:
    """FedProx: grad += mu * (w - w_global)  (gradient of mu/2||w - w_g||²)."""
    if mu == 0.0:
        return grads
    return jax.tree_util.tree_map(
        lambda g, p, gp: g + mu * (p - gp).astype(g.dtype),
        grads, params, global_params)


def global_norm(tree: Pytree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads: Pytree, max_norm: float) -> Pytree:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads)
