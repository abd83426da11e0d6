"""AdamW on pytrees (no optax offline) — state shards like the params."""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import scopes

__all__ = ["AdamWState", "adamw_init", "adamw_update"]


class AdamWState(NamedTuple):
    m: Any
    v: Any
    count: jnp.ndarray


def adamw_init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamWState(
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
        count=jnp.zeros((), jnp.int32),
    )


@jax.named_scope(scopes.OPTIMIZER)
def adamw_update(
    grads,
    state: AdamWState,
    params,
    lr,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
):
    count = state.count + 1
    if grad_clip is not None:
        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
        )
        scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    else:
        gnorm = jnp.zeros(())

    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32), state.m, grads)
    v = jax.tree.map(
        lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)), state.v, grads
    )
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(p, m_, v_):
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        return (p.astype(jnp.float32) - lr * (step + weight_decay * p.astype(jnp.float32))).astype(p.dtype)

    new_params = jax.tree.map(upd, params, m, v)
    return new_params, AdamWState(m=m, v=v, count=count), {"grad_norm": gnorm}
