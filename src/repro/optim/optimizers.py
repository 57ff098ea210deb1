"""Optimizers, written as pure pytree transforms (no optax dependency).

``rmsprop`` is the paper's optimizer (RMSprop, lr=1e-3, §IV-A); ``adamw``
serves the LM training path. Both keep f32 accumulator state regardless of
the (possibly bf16) parameter dtype — the "f32 master state" half of the
mixed-precision recipe; parameters themselves stay in their stored dtype
with the update computed in f32 and cast back.

State layout mirrors the parameter pytree (one accumulator leaf per param
leaf), so the same NamedSharding tree shards params and optimizer state
identically — required for the multi-pod dry-run to fit.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import scopes


# ---------------------------------------------------------------------------
# Utilities
# ---------------------------------------------------------------------------

def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads, max_norm: float):
    """Scale the whole gradient tree so its global norm is ≤ max_norm."""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# RMSprop (paper §IV-A: lr = 1e-3)
# ---------------------------------------------------------------------------

def rmsprop_init(params):
    """Square-average accumulator, f32, same tree as params."""
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)


def rmsprop(params, grads, state, *, lr: float = 1e-3, decay: float = 0.99,
            eps: float = 1e-8):
    """One RMSprop step. Returns (new_params, new_state)."""

    def upd(p, g, s):
        g32 = g.astype(jnp.float32)
        s = decay * s + (1.0 - decay) * jnp.square(g32)
        step = lr * g32 / (jnp.sqrt(s) + eps)
        return (p.astype(jnp.float32) - step).astype(p.dtype), s

    with jax.named_scope(scopes.RMSPROP):
        out = jax.tree.map(upd, params, grads, state)
    new_params = jax.tree.map(lambda o: o[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_state = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
    return new_params, new_state


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

class AdamWState(NamedTuple):
    mu: Any         # first moment, f32 tree
    nu: Any         # second moment, f32 tree
    count: jax.Array


def adamw_init(params) -> AdamWState:
    z = lambda p: jnp.zeros_like(p, jnp.float32)
    return AdamWState(mu=jax.tree.map(z, params),
                      nu=jax.tree.map(z, params),
                      count=jnp.zeros((), jnp.int32))


def adamw(params, grads, state: AdamWState, *, lr: float = 3e-4,
          b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.1):
    """One AdamW step. Returns (new_params, new_state)."""
    count = state.count + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m = b1 * m + (1.0 - b1) * g32
        v = b2 * v + (1.0 - b2) * jnp.square(g32)
        step = lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
        p32 = p.astype(jnp.float32)
        p32 = p32 - step - lr * weight_decay * p32
        return p32.astype(p.dtype), m, v

    with jax.named_scope(scopes.ADAMW):
        out = jax.tree.map(upd, params, grads, state.mu, state.nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), AdamWState(mu=pick(1), nu=pick(2), count=count)
