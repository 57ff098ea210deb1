"""Plain float32 semantics of one FLGW layer on the grouped path.

A layer ``W (M, N)`` carries grouping scores ``IG (M, G)`` and ``OG (G, N)``
(LearningGroup §III-A, after Wang et al., CVPR'19). The grouped path deals
rows and columns into ``G`` groups of equal capacity ``cap = ceil(ceil(M/G)
* slack)``: items sorted by (preferred group ascending, preference strength
descending, index ascending); each group keeps its ``cap`` most confident
items, and the overflow takes the free slots in ascending slot order. The
layer computes ``y = x @ (W * Mask)`` with ``Mask[i, j] = group(i) ==
group(j)``.

Gradients: ``dx`` and ``dW`` are exact for that product (``dW`` is zero off
the mask). ``IG``/``OG`` get the sparse-restricted straight-through
estimate: with ``s_row[i] = sum_j Mask[i, j] * dW_full[i, j] * W[i, j]``,
``dIG[i] = s_row[i] / tau * soft[i, g_i] * (onehot(g_i) - soft[i])``
where ``soft = softmax(IG / tau)`` and ``g_i`` is the group row ``i`` was
dealt to; ``dOG`` likewise over columns.

Nothing here is imported from the program under test.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def capacity(m: int, g: int, slack: float) -> int:
    cap = max(1, -(-m // g))
    return min(m, math.ceil(cap * slack)) if slack > 1.0 else cap


def deal(scores, slack: float):
    """(M, G) scores -> (M,) int32 group each item is dealt to."""
    m, g = scores.shape
    cap = capacity(m, g, slack)
    pref = jnp.argmax(scores, axis=1)
    strength = jnp.max(scores, axis=1)
    order = jnp.lexsort((jnp.arange(m), -strength, pref))
    pref_sorted = pref[order]
    first = jnp.searchsorted(pref_sorted, jnp.arange(g))
    rank = jnp.arange(m) - first[pref_sorted]
    keep = rank < cap
    kept_slot = pref_sorted * cap + jnp.minimum(rank, cap - 1)
    counts = jnp.minimum(jnp.bincount(pref, length=g), cap)
    slots = jnp.arange(g * cap)
    free = (slots % cap) >= counts[slots // cap]
    free_slots = jnp.argsort(~free, stable=True)
    overflow_rank = jnp.cumsum(~keep) - 1
    slot = jnp.where(keep, kept_slot,
                     free_slots[jnp.clip(overflow_rank, 0, g * cap - 1)])
    group_sorted = (slot // cap).astype(jnp.int32)
    return jnp.zeros((m,), jnp.int32).at[order].set(group_sorted)


def masks(ig, og, slack: float):
    """Row groups, column groups and the (M, N) mask of one layer."""
    rg = deal(jax.lax.stop_gradient(ig), slack)
    cg = deal(jax.lax.stop_gradient(og).T, slack)
    return rg, cg, (rg[:, None] == cg[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def linear(x, w, ig, og, slack: float, tau: float, mm):
    """``x @ (W * Mask)`` for ``x`` of shape (rows, M); ``mm`` is the
    matrix product to use (it sets the precision)."""
    _, _, mask = masks(ig, og, slack)
    return mm(x, jnp.where(mask, w, 0).astype(w.dtype))


def _fwd(x, w, ig, og, slack, tau, mm):
    return linear(x, w, ig, og, slack, tau, mm), (x, w, ig, og)


def _bwd(slack, tau, mm, res, gy):
    x, w, ig, og = res
    rg, cg, mask = masks(ig, og, slack)
    wm = jnp.where(mask, w, 0).astype(w.dtype)
    dx = mm(gy, wm.T).astype(x.dtype)
    dw_full = mm(x.T, gy)
    dw = jnp.where(mask, dw_full, 0).astype(w.dtype)
    contrib = jnp.where(mask, dw_full * w, 0).astype(jnp.float32)
    s_row, s_col = contrib.sum(1), contrib.sum(0)
    g = ig.shape[1]
    soft_ig = jax.nn.softmax(ig.astype(jnp.float32) / tau, axis=1)
    oh_r = jax.nn.one_hot(rg, g, dtype=jnp.float32)
    sel_r = jnp.sum(soft_ig * oh_r, axis=1, keepdims=True)
    dig = (s_row[:, None] / tau) * sel_r * (oh_r - soft_ig)
    soft_og = jax.nn.softmax(og.astype(jnp.float32) / tau, axis=0)
    oh_c = jax.nn.one_hot(cg, g, dtype=jnp.float32, axis=0)
    sel_c = jnp.sum(soft_og * oh_c, axis=0, keepdims=True)
    dog = (s_col[None, :] / tau) * sel_c * (oh_c - soft_og)
    return dx, dw, dig.astype(ig.dtype), dog.astype(og.dtype)


linear.defvjp(_fwd, _bwd)
