"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1), with
YaRN RoPE scaling (arXiv:2309.00071).

Keys and values come from one latent per token: ``[c | k_pe] = x W_kv_a``,
``c`` is RMS-normalised and expanded per head to ``[k_nope | v] = c
W_kv_b``; the rotary key ``k_pe`` is one per token, shared by all heads.
The query has no low-rank compression (DeepSeek-V2-Lite's ``q_lora_rank``
is null): ``[q_nope | q_pe] = x W_q`` per head. Query and key heads are
``qk_nope_dim + qk_rope_dim`` wide, values ``v_head_dim``.

RoPE rotates the pairs (i, i + D/2) of the rope columns (the repo's
``layers.rope``); DeepSeek's checkpoints pair (2i, 2i + 1), which is a
fixed permutation of the rope columns of ``W_q`` and ``W_kv_a``. Random
weights make the two layouts the same model.

Training and prefill run the attention core through the flash kernel
(``cfg.use_flash``) or, at test sizes, through the plain masked softmax;
decode caches the normalised latent and the rotated key (``r + qk_rope``
numbers a token) and expands them through ``W_kv_b`` each step.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.flgw import FLGWConfig
from repro.models import attention as attn_mod
from repro.models.layers import (dense_init, plan_of, proj, rmsnorm,
                                 rmsnorm_init, rope)


# YaRN's ramp ends, in rotations over the original context: the published
# values, which DeepSeek-V2 keeps. Its ``mscale`` equals ``mscale_all_dim``,
# so the factor on cos and sin (their ratio) is 1 and only the softmax
# scale carries ``mscale_all_dim``.
YARN_BETA_FAST, YARN_BETA_SLOW = 32, 1


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg) -> jax.Array:
    """Rotary frequencies of the ``qk_rope_dim`` columns: the plain
    ``theta ** (-2i / D)`` for the fast dimensions, divided by the
    factor for the slow ones, and a linear ramp between the two
    correction dimensions of ``beta_fast`` and ``beta_slow`` rotations
    over the original context."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.yarn_factor <= 1:
        return extra

    def corr(rot):
        return (dim * math.log(cfg.yarn_original_max / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(YARN_BETA_FAST)), 0)
    high = min(math.ceil(corr(YARN_BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    keep = 1.0 - ramp                         # 1: extrapolate (fast dims)
    return extra / cfg.yarn_factor * (1 - keep) + extra * keep


def softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale_all_dim``
    attention factor squared."""
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_mscale_all_dim else 1.0)
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def mla_init(key, cfg, *, flgw: Optional[FLGWConfig] = None):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    params["q"], specs["q"] = dense_init(
        ks[0], d, h * qk, flgw=flgw, axes=("embed", "heads"), dtype=cfg.dtype)
    params["kv_a"], specs["kv_a"] = dense_init(
        ks[1], d, r + cfg.qk_rope_dim, flgw=flgw, axes=("embed", None),
        dtype=cfg.dtype)
    params["kv_norm"], specs["kv_norm"] = rmsnorm_init(r, cfg.norm_plus_one)
    params["kv_b"], specs["kv_b"] = dense_init(
        ks[2], r, h * (cfg.qk_nope_dim + cfg.v_head_dim), flgw=flgw,
        axes=(None, "heads"), dtype=cfg.dtype)
    params["o"], specs["o"] = dense_init(
        ks[3], h * cfg.v_head_dim, d, flgw=flgw, axes=("heads", "embed"),
        dtype=cfg.dtype)
    return params, specs


def _rotate(x, positions, cfg):
    return rope(x, positions, freqs=yarn_inv_freq(cfg))


def _expand(p, c, k_pe, cfg, flgw, plans):
    """Per-head keys ``[k_nope | k_pe]`` and values from the latent."""
    b, t = c.shape[:2]
    h, dn = cfg.n_heads, cfg.qk_nope_dim
    kv = proj(p["kv_b"], c, flgw, plan=plan_of(plans, "kv_b")).reshape(
        b, t, h, dn + cfg.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (b, t, h, cfg.qk_rope_dim))],
        axis=-1)
    return k, kv[..., dn:]


def mla(p, x, positions, cfg, *, cache: Optional[dict] = None,
        flash: bool = False, flgw: Optional[FLGWConfig] = None, plans=None):
    """Returns (out, new_cache). ``cache = {"c", "k_pe", "pos"}`` is the
    decode path; None is training and prefill, causal over the sequence."""
    b, s, _ = x.shape
    h, dn, r = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    q = proj(p["q"], x, flgw, plan=plan_of(plans, "q")).reshape(
        b, s, h, dn + cfg.qk_rope_dim)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], positions, cfg)],
                        axis=-1)
    kv_a = proj(p["kv_a"], x, flgw, plan=plan_of(plans, "kv_a"))
    c = rmsnorm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps, cfg.norm_plus_one)
    k_pe = _rotate(kv_a[..., None, r:], positions, cfg)      # (b, s, 1, dr)
    scale = softmax_scale(cfg)

    new_cache = None
    if cache is not None:
        bufs, k_pos, k_valid = attn_mod.ring_write(
            {"c": cache["c"], "k_pe": cache["k_pe"]},
            {"c": c, "k_pe": k_pe[:, :, 0]}, cache["pos"])
        new_cache = dict(bufs, pos=cache["pos"] + s)
        k, v = _expand(p, bufs["c"], bufs["k_pe"][:, :, None], cfg, flgw,
                       plans)
        mask = attn_mod._mask(positions, k_pos, causal=True, window=0,
                              prefix_len=0, k_valid=k_valid)
    else:
        k, v = _expand(p, c, k_pe, cfg, flgw, plans)
        mask = attn_mod._mask(positions, positions, causal=True, window=0,
                              prefix_len=0)
    if flash and cache is None:
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), True, 0, 0.0, scale,
                              512, 512, None).transpose(0, 2, 1, 3)
    else:
        out = attn_mod._attend(q[:, :, :, None], k, v, mask, cfg,
                               scale=scale)[:, :, :, 0]
    out = out.reshape(b, s, h * cfg.v_head_dim)
    return proj(p["o"], out, flgw, plan=plan_of(plans, "o")), new_cache
