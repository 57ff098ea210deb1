"""GQA attention: RoPE, sliding window, logit softcap, prefix-LM masking,
KV-cache decode, cross-attention — query-chunked for bounded memory.

The training/prefill path scans over query chunks so the materialized logit
tile is (B, Hkv, q_per_kv, Cq, T) instead of the full S×T square — this keeps
32k-sequence prefill inside per-device HBM without a fused kernel, while HLO
FLOP accounting stays exact for the roofline. ``banded=True`` additionally
restricts each query chunk of a sliding-window layer to its reachable KV band
(exact, FLOPs ÷ ~S/window) — used by the perf path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.flgw import FLGWConfig
from repro.models.layers import dense_init, plan_of, proj, rope, softcap

NEG_INF = -2.3819763e38  # == jnp.finfo(jnp.float32).min-ish, matches XLA


def attn_init(key, cfg, *, flgw: Optional[FLGWConfig] = None):
    d, h = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    params["q"], specs["q"] = dense_init(
        ks[0], d, cfg.n_heads * h, flgw=flgw, axes=("embed", "heads"),
        dtype=cfg.dtype)
    params["k"], specs["k"] = dense_init(
        ks[1], d, cfg.n_kv_heads * h, flgw=flgw, axes=("embed", "kv_heads"),
        dtype=cfg.dtype)
    params["v"], specs["v"] = dense_init(
        ks[2], d, cfg.n_kv_heads * h, flgw=flgw, axes=("embed", "kv_heads"),
        dtype=cfg.dtype)
    params["o"], specs["o"] = dense_init(
        ks[3], cfg.n_heads * h, d, flgw=flgw, axes=("heads", "embed"),
        dtype=cfg.dtype)
    return params, specs


def _mask(q_pos, k_pos, *, causal: bool, window: int, prefix_len: int,
          k_valid=None):
    """(..., Sq, Sk) boolean allowed-attention mask from position vectors."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    if causal:
        allowed = k <= q
        if prefix_len > 0:
            allowed = allowed | ((k < prefix_len) & (q < prefix_len))
    else:
        allowed = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if window > 0:
        allowed = allowed & (k > q - window)
    if k_valid is not None:
        allowed = allowed & k_valid[..., None, :]
    return allowed


def _attend(q, k, v, mask, cfg, scale=None):
    """q: (B, Sq, G, Q, D); k: (B, Sk, G, D); v: (B, Sk, G, Dv); mask:
    (B, Sq, Sk) or (Sq, Sk). ``scale`` defaults to ``head_dim ** -0.5``."""
    scale = cfg.head_dim ** -0.5 if scale is None else scale
    logits = jnp.einsum("bsgqd,btgd->bgqst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if cfg.attn_softcap > 0:
        logits = softcap(logits, cfg.attn_softcap)
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgqst,btgd->bsgqd", probs, v)
    return out


def ring_write(bufs: dict, new: dict, pos):
    """Write ``new[name]`` (B, s, ...) into each decode ring buffer
    ``bufs[name]`` (B, L, ...) at ``pos % L``. Returns (bufs, k_pos,
    k_valid): the absolute position each ring slot holds after the write,
    and whether it was ever written.

    A scalar ``pos`` is the lockstep cache: every batch row shares one
    stream offset. A (B,) ``pos`` is the per-slot cache
    (``init_cache(per_slot=True)``): each row is an independent request
    stream at its own offset, the continuous-batching contract, written
    one token at a time; a slot reset to pos=0 invalidates its stale
    entries for free (every unwritten ring index maps to a negative
    absolute position).
    """
    t = next(iter(bufs.values())).shape[1]
    s = next(iter(new.values())).shape[1]
    idx = jnp.arange(t, dtype=jnp.int32)
    write = pos % t
    if jnp.ndim(pos) == 0:
        out = {n: jax.lax.dynamic_update_slice_in_dim(bufs[n], new[n], write,
                                                      axis=1) for n in bufs}
        # Absolute position held by each ring slot after the write: the
        # largest p ≤ pos with p ≡ idx (mod L); negative ⇒ never written.
        k_pos = (pos - jnp.mod(pos - idx, t))[None]
    else:
        if s != 1:
            raise ValueError(
                "per-slot decode caches take single-token steps "
                f"(got {s} tokens); multi-token prefill goes through "
                "the cache-free path one token at a time")
        rows = jnp.arange(pos.shape[0])
        out = {n: bufs[n].at[rows, write].set(new[n][:, 0]) for n in bufs}
        k_pos = pos[:, None] - jnp.mod(pos[:, None] - idx[None], t)
    return out, k_pos, k_pos >= 0


def _split_heads(x, n_kv, q_per_kv, hd):
    b, s = x.shape[:2]
    return x.reshape(b, s, n_kv, q_per_kv, hd)


def attention(p, x, positions, cfg, *, window: int = 0, causal: bool = True,
              prefix_len: int = 0, kv_x: Optional[jax.Array] = None,
              cache: Optional[dict] = None, q_chunk: int = 512,
              banded: bool = False, flash: bool = False,
              core_identity: bool = False,
              flgw: Optional[FLGWConfig] = None, plans=None):
    """Returns (out, new_cache).

    * training/prefill: ``cache is None`` — full-sequence, query-chunked.
    * decode: ``cache = {"k","v","pos"}`` — insert one (or few) tokens at
      ``cache["pos"]`` and attend over the cache.
    * cross-attention: ``kv_x`` given — keys/values from the encoder stream,
      no causal mask, no RoPE on k (positions of memory are absolute).

    ``plans``: this attention layer's entry of a cached PlanState — one
    GroupPlan per q/k/v/o projection on the FLGW grouped path (None falls
    back to per-call re-encoding inside ``proj``).
    """
    b, s, _ = x.shape
    hd, n_kv, qpk = cfg.head_dim, cfg.n_kv_heads, cfg.q_per_kv
    q = proj(p["q"], x, flgw, plan=plan_of(plans, "q")
             ).reshape(b, s, n_kv, qpk, hd)
    src = x if kv_x is None else kv_x
    k = proj(p["k"], src, flgw, plan=plan_of(plans, "k")
             ).reshape(b, src.shape[1], n_kv, hd)
    v = proj(p["v"], src, flgw, plan=plan_of(plans, "v")
             ).reshape(b, src.shape[1], n_kv, hd)

    if kv_x is None:
        q = rope(q.reshape(b, s, n_kv * qpk, hd), positions,
                 cfg.rope_theta).reshape(b, s, n_kv, qpk, hd)
        k = rope(k, positions if cache is None else positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        # Decode: ring-buffer write at ``pos % L``. Windowed slots allocate
        # L = min(max_seq, window) (init_cache), so sliding-window layers
        # keep O(window) memory at any context length — this is what makes
        # the 500k-context cells runnable for SWA/local-attention archs.
        # When L covers the whole stream, pos % L == pos and this reduces to
        # the plain append-at-pos cache. Single-token writes only (s == 1
        # in the decode cells); multi-token prefill goes through the
        # cache-free path.
        pos = cache["pos"]
        bufs, k_pos, k_valid = ring_write({"k": cache["k"], "v": cache["v"]},
                                          {"k": k, "v": v}, pos)
        ck, cv = bufs["k"], bufs["v"]
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        mask = _mask(positions, k_pos, causal=causal, window=window,
                     prefix_len=prefix_len, k_valid=k_valid)
        out = _attend(q, ck, cv, mask, cfg)
        out = out.reshape(b, s, n_kv * qpk * hd)
        return proj(p["o"], out, flgw, plan=plan_of(plans, "o")), new_cache

    if core_identity and cache is None:
        # Dry-run cost variant: skip ONLY the attention core (projections,
        # RoPE stay). Subtracting this variant's measured cost from the
        # normal one isolates the core's HLO contribution, which the flash
        # accounting replaces with the fused-kernel analytic model.
        out = q.reshape(b, s, -1)
        return proj(p["o"], out, flgw, plan=plan_of(plans, "o")), None

    # Training / prefill: fused Pallas path when applicable (self-attention,
    # positions are the plain 0..S-1 ramp, no bidirectional prefix). The
    # kernel never materializes the (S, T) logits — see kernels/flash_attention.
    if (flash and kv_x is None and prefix_len == 0 and causal):
        from repro.kernels.flash_attention.ops import flash_attention
        qf = q.reshape(b, s, n_kv * qpk, hd).transpose(0, 2, 1, 3)
        kf = k.transpose(0, 2, 1, 3)
        vf = v.transpose(0, 2, 1, 3)
        of = flash_attention(qf, kf, vf, True, window,
                             float(cfg.attn_softcap), None, 512, 512, None)
        out = of.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return proj(p["o"], out, flgw, plan=plan_of(plans, "o")), None

    # Training / prefill: scan over query chunks for bounded memory.
    t = src.shape[1]
    k_pos_full = positions if kv_x is None else jnp.arange(t, dtype=jnp.int32)[None]
    if s <= q_chunk:
        mask = _mask(positions, k_pos_full, causal=causal and kv_x is None,
                     window=window, prefix_len=prefix_len)
        out = _attend(q, k, v, mask, cfg)
        return proj(p["o"], out.reshape(b, s, -1), flgw, plan=plan_of(plans, "o")), None

    if s % q_chunk != 0:   # e.g. VLM prefix extends S; pick a clean divisor
        q_chunk = next(c for c in range(q_chunk, 0, -1) if s % c == 0)
    n_chunks = s // q_chunk
    qc = q.reshape(b, n_chunks, q_chunk, n_kv, qpk, hd).transpose(1, 0, 2, 3, 4, 5)
    pc = positions.reshape(b, n_chunks, q_chunk).transpose(1, 0, 2)

    use_band = banded and window > 0 and kv_x is None
    band = None
    if use_band:
        # KV band reachable by one query chunk: window + chunk, rounded to
        # chunk granularity (exact — outside the band everything is masked).
        band = min(t, ((window + q_chunk - 1) // q_chunk + 1) * q_chunk)

    def body(carry, inp):
        ci, q_i, p_i = inp
        if use_band:
            start = jnp.maximum(ci * q_chunk + q_chunk - band, 0)
            k_i = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            v_i = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            kp_i = jax.lax.dynamic_slice_in_dim(k_pos_full, start, band,
                                                axis=-1)
        else:
            k_i, v_i, kp_i = k, v, k_pos_full
        m = _mask(p_i, kp_i, causal=causal and kv_x is None, window=window,
                  prefix_len=prefix_len)
        o = _attend(q_i, k_i, v_i, m, cfg)
        return carry, o

    idx = jnp.arange(n_chunks, dtype=jnp.int32)
    _, outs = jax.lax.scan(body, None, (idx, qc, pc))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, -1)
    return proj(p["o"], out, flgw, plan=plan_of(plans, "o")), None
