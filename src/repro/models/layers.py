"""Layer primitives shared by all architectures.

Every projection goes through ``proj`` which dispatches on the presence of
FLGW grouping parameters — the paper's pruning technique is a first-class
feature of every linear layer in the framework, not a bolt-on.

Parameters are plain pytrees (nested dicts); initializers return
``(params, specs)`` where ``specs`` mirrors the tree with logical sharding
axis names consumed by ``repro.sharding.partition``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.encoder import PlanState as EncoderPlanState
from repro.core.flgw import FLGWConfig, init_grouping, mask_ste
from repro.core.grouped import GroupPlan, grouped_apply
from repro.sharding.partition import constrain


# ---------------------------------------------------------------------------
# Dense / FLGW projection
# ---------------------------------------------------------------------------

def dense_init(key, m: int, n: int, *, flgw: Optional[FLGWConfig] = None,
               axes=("in", "out"), dtype=jnp.bfloat16, scale: float = 1.0):
    """One projection W: (m, n), optionally carrying FLGW grouping params."""
    kw, kg = jax.random.split(key)
    std = scale / (m ** 0.5)
    params = {"w": (jax.random.normal(kw, (m, n), jnp.float32) * std
                    ).astype(dtype)}
    specs = {"w": axes}
    if flgw is not None and flgw.groups > 1:
        g = init_grouping(kg, m, n, flgw.groups, jnp.float32)
        params["ig"] = g["ig"]
        params["og"] = g["og"]
        specs["ig"] = (axes[0], None)
        specs["og"] = (None, axes[1])
    return params, specs


def runs_kernel(p: dict, flgw: Optional[FLGWConfig]) -> bool:
    """Whether :func:`proj` computes this layer with the grouped kernel
    (a custom-VJP Pallas call) rather than a plain product."""
    return (flgw is not None and flgw.enabled and "ig" in p
            and flgw.path == "grouped")


def proj(p: dict, x: jax.Array, flgw: Optional[FLGWConfig] = None,
         *, transpose: bool = False,
         plan: Optional[GroupPlan] = None) -> jax.Array:
    """y = x @ W (or x @ W^T), FLGW-masked when grouping params exist.

    ``plan`` is this layer's cached sparse metadata for the grouped path
    (one entry of an ``encode_plans`` PlanState); ``None`` falls back to
    re-encoding inside the projection — correct but unamortized.
    """
    w = p["w"]
    if flgw is None or not flgw.enabled or "ig" not in p:
        return x @ (w.T if transpose else w)
    if runs_kernel(p, flgw):
        return grouped_apply(x, w, p["ig"], p["og"], flgw,
                             transpose=transpose, plan=plan)
    mask = mask_ste(p["ig"], p["og"], flgw.ste_temperature).astype(w.dtype)
    wm = w * mask
    return x @ (wm.T if transpose else wm)


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, plus_one: bool = True):
    """The weight is ``1 + scale`` with scale from 0 (Gemma) when
    ``plus_one``, else ``scale`` itself from 1 (Llama, DeepSeek)."""
    scale = jnp.zeros((d,), jnp.float32) if plus_one else \
        jnp.ones((d,), jnp.float32)
    return {"scale": scale}, {"scale": (None,)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6,
            plus_one: bool = True) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = 1.0 + p["scale"] if plus_one else p["scale"]
    return (x * w).astype(dt)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    e = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    return {"embedding": e.astype(dtype)}, {"embedding": ("vocab", "embed")}


def head_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    """An output head apart from the embedding, (vocab, d) like it."""
    w = jax.random.normal(key, (vocab, d), jnp.float32) * d ** -0.5
    return {"w": w.astype(dtype)}, {"w": ("vocab", "embed")}


def embed(p: dict, tokens: jax.Array, d_model: int,
          scale: bool = True) -> jax.Array:
    # Gemma-style sqrt(d) scaling keeps the residual stream O(1).
    x = p["embedding"][tokens]
    if scale:
        x = x * jnp.asarray(d_model ** 0.5, p["embedding"].dtype)
    return x


def unembed(w: jax.Array, x: jax.Array) -> jax.Array:
    """Logits from the (vocab, d) output matrix."""
    return x @ w.T


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         freqs: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S) int32. Rotates the pairs
    (i, i + D/2) by ``positions * freqs[i]``; ``freqs`` defaults to
    ``theta ** (-2i / D)``."""
    d = x.shape[-1]
    half = d // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (FLGW-capable)
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int, *, gated: bool = True,
             flgw: Optional[FLGWConfig] = None, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    params, specs = {}, {}
    params["up"], specs["up"] = dense_init(
        ks[0], d, ff, flgw=flgw, axes=("embed", "ffn"), dtype=dtype)
    if gated:
        params["gate"], specs["gate"] = dense_init(
            ks[1], d, ff, flgw=flgw, axes=("embed", "ffn"), dtype=dtype)
    params["down"], specs["down"] = dense_init(
        ks[2], ff, d, flgw=flgw, axes=("ffn", "embed"), dtype=dtype)
    return params, specs


def plan_of(plans, name: str) -> Optional[GroupPlan]:
    """Look one entry out of a PlanState / nested plans dict (None when
    absent). Accepts the ``repro.core.encoder.PlanState`` wrapper, the raw
    nested dict, or None; the result is a GroupPlan at leaf level or a
    sub-dict for nested lookups."""
    if isinstance(plans, EncoderPlanState):
        plans = plans.plans
    if not plans:
        return None
    return plans.get(name)


ACTIVATIONS = {"gelu_tanh": functools.partial(jax.nn.gelu, approximate=True),
               "silu": jax.nn.silu}


def mlp(p: dict, x: jax.Array, flgw: Optional[FLGWConfig] = None,
        plans: Optional[dict] = None, act: str = "gelu_tanh") -> jax.Array:
    fn = ACTIVATIONS[act]
    up = proj(p["up"], x, flgw, plan=plan_of(plans, "up"))
    if "gate" in p:
        up = fn(proj(p["gate"], x, flgw, plan=plan_of(plans, "gate"))) * up
    else:
        up = fn(up)
    up = constrain(up, ("batch", None, "ffn"))   # TP on the hidden dim
    return proj(p["down"], up, flgw, plan=plan_of(plans, "down"))
