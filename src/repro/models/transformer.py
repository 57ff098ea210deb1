"""Pattern-scanned transformer assembly for every assigned architecture.

The stack is ``lax.scan`` over ``n_blocks`` macro-blocks; inside the body the
``period`` slots of ``cfg.pattern`` are unrolled with their static types
(attn/ssm mixer, window size, mlp/moe/moe_dense FFN, optional cross-attn).
Per-slot parameters and KV/SSM caches are stacked on axis 0 and scanned.
This keeps HLO size O(period), not O(n_layers) — critical for compiling 10
architectures × 2 meshes on one host.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import encoder as planenc
from repro.core.flgw import FLGWConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig, SlotSpec
from repro.models.layers import (embed, embed_init, mlp, mlp_init, plan_of,
                                 rmsnorm, rmsnorm_init, softcap, unembed)
from repro.sharding.partition import constrain


def _flgw_cfg(cfg: ModelConfig, target: str) -> Optional[FLGWConfig]:
    if not cfg.flgw_on(target):
        return None
    return FLGWConfig(groups=cfg.flgw_groups, path=cfg.flgw_path)


def encode_plans(params, cfg: ModelConfig) -> planenc.PlanState:
    """One OSEL-analogue pass over the LM stack's FLGW projections.

    Plans for the scanned decoder blocks come back stacked along the
    ``n_blocks`` axis (mirroring the stacked params) and ride the block
    scan as per-block xs; the empty state is returned unless the compact
    ``grouped`` path is active.
    """
    if cfg.flgw_groups <= 1 or cfg.flgw_path != "grouped":
        return planenc.empty_state()
    return planenc.encode_plans(
        params, FLGWConfig(groups=cfg.flgw_groups, path=cfg.flgw_path))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _slot_init(key, cfg: ModelConfig, slot: SlotSpec):
    ks = jax.random.split(key, 8)
    p, s = {}, {}
    p["norm1"], s["norm1"] = rmsnorm_init(cfg.d_model)
    if slot.mixer == "attn":
        p["mixer"], s["mixer"] = attn_mod.attn_init(
            ks[0], cfg, flgw=_flgw_cfg(cfg, "attn"))
    else:
        p["mixer"], s["mixer"] = ssm_mod.ssm_init(
            ks[0], cfg, flgw=_flgw_cfg(cfg, "ssm"))
    if slot.cross:
        p["norm_x"], s["norm_x"] = rmsnorm_init(cfg.d_model)
        p["cross"], s["cross"] = attn_mod.attn_init(
            ks[1], cfg, flgw=_flgw_cfg(cfg, "attn"))
    if slot.ffn == "none":
        return p, s
    p["norm2"], s["norm2"] = rmsnorm_init(cfg.d_model)
    if slot.ffn == "mlp":
        p["ffn"], s["ffn"] = mlp_init(
            ks[2], cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
            flgw=_flgw_cfg(cfg, "mlp"), dtype=cfg.dtype)
    else:
        p["moe"], s["moe"] = moe_mod.moe_init(
            ks[3], cfg, flgw=_flgw_cfg(cfg, "moe"))
        if slot.ffn == "moe_dense":
            p["ffn"], s["ffn"] = mlp_init(
                ks[4], cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
                flgw=_flgw_cfg(cfg, "mlp"), dtype=cfg.dtype)
    return p, s


def _stacked_slot_init(key, cfg: ModelConfig, slot: SlotSpec, n: int):
    keys = jax.random.split(key, n)
    spec_box = {}

    def init_one(k):
        p, s = _slot_init(k, cfg, slot)
        spec_box["spec"] = s            # static — captured during tracing
        return p

    params = jax.vmap(init_one)(keys)
    # prepend the "layers" (scan) axis to every leaf spec
    spec = jax.tree.map(lambda a: ("layers",) + tuple(a), spec_box["spec"],
                        is_leaf=lambda a: isinstance(a, tuple)
                        and all(isinstance(x, (str, type(None))) for x in a))
    return params, spec


def _blocks_init(key, cfg: ModelConfig, pattern, n_blocks: int):
    params, specs = {}, {}
    keys = jax.random.split(key, len(pattern))
    for i, slot in enumerate(pattern):
        params[f"slot{i}"], specs[f"slot{i}"] = _stacked_slot_init(
            keys[i], cfg, slot, n_blocks)
    return params, specs


def lm_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    params["embed"], specs["embed"] = embed_init(
        ks[0], cfg.vocab, cfg.d_model, cfg.dtype)
    params["blocks"], specs["blocks"] = _blocks_init(
        ks[1], cfg, cfg.pattern, cfg.n_blocks)
    params["final_norm"], specs["final_norm"] = rmsnorm_init(cfg.d_model)
    if cfg.encoder_layers:
        enc_slot = SlotSpec(mixer="attn", window=0, ffn="mlp", causal=False)
        params["encoder"], specs["encoder"] = _blocks_init(
            ks[2], cfg, (enc_slot,), cfg.encoder_layers)
        params["enc_norm"], specs["enc_norm"] = rmsnorm_init(cfg.d_model)
    return params, specs


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _slot_apply(p, x, positions, cfg: ModelConfig, slot: SlotSpec, *,
                cache=None, pos=None, encoder_out=None, prefix_len=0,
                q_chunk=512, banded=False, ssd_unroll=False,
                moe_dropless=False, attn_identity=False, plans=None):
    """``plans``: this slot's entry of the (sliced) PlanState — cached
    FLGW metadata for *every* FLGW target the slot carries: the
    attention/SSM mixer, the cross-attention, the MoE experts and the
    ``ffn`` projections all consume their own plan subtree, so no mixer
    ever falls back to per-call re-encoding when a PlanState is supplied.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if slot.mixer == "attn":
        c = None
        if cache is not None:
            c = {"k": cache["k"], "v": cache["v"], "pos": pos}
        h, nc = attn_mod.attention(
            p["mixer"], h, positions, cfg, window=slot.window,
            causal=slot.causal, prefix_len=prefix_len, cache=c,
            q_chunk=q_chunk, banded=banded, flash=cfg.use_flash,
            core_identity=attn_identity, flgw=_flgw_cfg(cfg, "attn"),
            plans=plan_of(plans, "mixer"))
        if nc is not None:
            new_cache.update({"k": nc["k"], "v": nc["v"]})
    else:
        h, nc = ssm_mod.ssm(p["mixer"], h, cfg, cache=cache and
                            {"state": cache["state"], "conv": cache["conv"]},
                            chunk=cfg.ssm_chunk,
                            flgw=_flgw_cfg(cfg, "ssm"), unroll=ssd_unroll,
                            plans=plan_of(plans, "mixer"))
        if nc is not None:
            new_cache.update(nc)
    x = x + h
    if slot.cross:
        h = rmsnorm(p["norm_x"], x, cfg.norm_eps)
        h, _ = attn_mod.attention(
            p["cross"], h, positions, cfg, causal=False, kv_x=encoder_out,
            q_chunk=q_chunk, flgw=_flgw_cfg(cfg, "attn"),
            plans=plan_of(plans, "cross"))
        x = x + h
    if slot.ffn == "none":     # pure-SSM blocks (mamba2) have no FFN
        return x, aux, new_cache
    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if slot.ffn == "mlp":
        h = mlp(p["ffn"], h, _flgw_cfg(cfg, "mlp"),
                plans=plan_of(plans, "ffn"))
    else:
        h, a = moe_mod.moe(p["moe"], h, cfg, flgw=_flgw_cfg(cfg, "moe"),
                           dropless=moe_dropless or cache is not None,
                           plans=plan_of(plans, "moe"))
        aux = aux + a
        if slot.ffn == "moe_dense":
            h = h + mlp(p["ffn"], rmsnorm(p["norm2"], x, cfg.norm_eps),
                        _flgw_cfg(cfg, "mlp"), plans=plan_of(plans, "ffn"))
    return x + h, aux, new_cache


def _apply_blocks(params, cfg: ModelConfig, pattern, x, positions, *,
                  caches=None, pos=None, encoder_out=None, prefix_len=0,
                  q_chunk=512, banded=False, remat=False, ssd_unroll=False,
                  unroll_blocks=False, moe_dropless=False,
                  attn_identity=False, plans=None):
    has_cache = caches is not None
    plans = plans or {}   # nested dict: slot{i} -> ffn -> stacked GroupPlans

    def body(carry, xs):
        x, aux = carry
        x = constrain(x, ("batch", None, None))   # keep batch data-parallel
        if has_cache:
            block_p, block_c, block_pl = xs
        else:
            (block_p, block_pl), block_c = xs, None
        new_c = {}
        for i, slot in enumerate(pattern):
            c_i = None if block_c is None else block_c.get(f"slot{i}")
            x, a, nc = _slot_apply(
                block_p[f"slot{i}"], x, positions, cfg, slot, cache=c_i,
                pos=pos, encoder_out=encoder_out, prefix_len=prefix_len,
                q_chunk=q_chunk, banded=banded, ssd_unroll=ssd_unroll,
                moe_dropless=moe_dropless, attn_identity=attn_identity,
                plans=plan_of(block_pl, f"slot{i}"))
            aux = aux + a
            if nc:
                new_c[f"slot{i}"] = nc
        return (x, aux), (new_c if new_c else None)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    aux0 = jnp.zeros((), jnp.float32)
    # plans ride the scan as per-block xs ({} contributes no leaves — the
    # stacked GroupPlans slice alongside their stacked params)
    xs = (params, caches, plans) if has_cache else (params, plans)

    if unroll_blocks:
        # Straight-line block loop — the dry-run cost variant. HLO cost
        # analysis counts a while-loop body once (fwd AND the reverse-scan
        # bwd), so the cost program must contain no loops at all.
        carry, outs = (x, aux0), []
        nb = jax.tree.leaves(params)[0].shape[0]
        for i in range(nb):
            xs_i = jax.tree.map(lambda a: a[i], xs)
            carry, o = body(carry, xs_i)
            outs.append(o)
        (x, aux) = carry
        new_caches = (None if outs[0] is None
                      else jax.tree.map(lambda *ls: jnp.stack(ls), *outs))
        return x, aux, new_caches

    (x, aux), new_caches = jax.lax.scan(body, (x, aux0), xs)
    return x, aux, new_caches


def lm_apply(params, cfg: ModelConfig, tokens, positions, *,
             patch_embeds=None, frames=None, cache=None, q_chunk=512,
             banded=False, remat=None, return_hidden=False,
             ssd_unroll=False, unroll_blocks=False, moe_dropless=False,
             attn_identity=False, plans=None):
    """Forward pass. Returns (logits, aux_loss, new_cache).

    tokens: (B, S) int32; positions: (B, S) int32.
    patch_embeds: (B, prefix, d) VLM stub prefix (prefill only).
    frames: (B, T, d) audio-stub encoder input (whisper).
    cache: decode caches from ``init_cache``.
    plans: cached FLGW metadata from :func:`encode_plans` (PlanState or its
    raw dict). When None, a ``plans`` entry riding the decode cache (see
    ``init_cache(..., params=...)``) is consumed instead — the serving
    contract: the PlanState lives beside the KV/SSM caches, encoded once
    at prefill and reused by every decode step. With neither, the grouped
    path falls back to per-projection re-encoding.
    return_hidden: skip unembedding — the training loss computes logits in
    sequence chunks (the full (B, S, vocab) tensor at 256k vocab never fits).
    """
    remat = cfg.remat if remat is None else remat
    if plans is None and cache is not None:
        plans = cache.get("plans")
    if isinstance(plans, planenc.PlanState):
        plans = plans.plans
    plans = plans or {}
    x = embed(params["embed"], tokens, cfg.d_model).astype(cfg.dtype)
    prefix_len = 0
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(cfg.dtype), x], axis=1)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        prefix_len = patch_embeds.shape[1]

    encoder_out = None
    if cfg.encoder_layers:
        if frames is not None:
            enc_slot = SlotSpec(mixer="attn", window=0, ffn="mlp", causal=False)
            enc_pos = jnp.broadcast_to(
                jnp.arange(frames.shape[1], dtype=jnp.int32)[None],
                frames.shape[:2])
            eo, _, _ = _apply_blocks(
                params["encoder"], cfg, (enc_slot,),
                frames.astype(cfg.dtype), enc_pos, q_chunk=q_chunk,
                remat=remat, ssd_unroll=ssd_unroll,
                unroll_blocks=unroll_blocks, plans=plans.get("encoder"))
            encoder_out = rmsnorm(params["enc_norm"], eo, cfg.norm_eps)
            # Encoder self-attn must be bidirectional: handled by window=0 &
            # causal mask relaxation below (prefix over the whole stream).
        elif cache is not None:
            encoder_out = cache["encoder_out"]

    pos = None if cache is None else cache["pos"]
    slot_caches = None if cache is None else cache["blocks"]
    x, aux, new_slot_caches = _apply_blocks(
        params["blocks"], cfg, cfg.pattern, x, positions, caches=slot_caches,
        pos=pos, encoder_out=encoder_out, prefix_len=prefix_len,
        q_chunk=q_chunk, banded=banded, remat=remat and cache is None,
        ssd_unroll=ssd_unroll, unroll_blocks=unroll_blocks,
        moe_dropless=moe_dropless, attn_identity=attn_identity,
        plans=plans.get("blocks"))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        out = x if prefix_len == 0 else x[:, prefix_len:]
    else:
        logits = unembed(params["embed"], x)
        out = softcap(logits.astype(jnp.float32), cfg.logit_softcap)

    new_cache = None
    if cache is not None:
        new_cache = {"pos": pos + tokens.shape[1], "blocks": new_slot_caches}
        if "plans" in cache:
            # plans ride the cache unchanged — params are frozen *within*
            # a request; across requests (online tuning) the serving loop
            # certifies them via refresh_cache_plans at the boundary
            new_cache["plans"] = cache["plans"]
        if encoder_out is not None:
            new_cache["encoder_out"] = encoder_out
    return out, aux, new_cache


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def _cache_len(slot: SlotSpec, max_seq: int) -> int:
    """KV length of one slot: sliding-window slots only ever see ``window``
    positions, so their ring buffer is bounded — O(window) memory per layer
    regardless of context length."""
    if slot.window > 0:
        return min(max_seq, slot.window)
    return max_seq


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None, *, params=None, per_slot: bool = False,
               compact: bool | None = None) -> dict:
    """Decode caches, stacked (n_blocks, ...) per slot.

    ``params``: pass the model params to cache a :class:`~repro.core.
    encoder.PlanState` beside the KV/SSM caches (``cache["plans"]``) on
    the FLGW grouped path — the one-encode-per-serve contract: prefill
    builds the plans here, every decode step consumes them through
    ``lm_apply``, and they ride the returned cache unchanged. Without
    params (or off the grouped path) ``cache["plans"]`` is ``()`` and
    grouped projections fall back to per-call re-encoding.

    ``compact``: also attach the compact weights (``GroupPlan.wc`` — the
    weight half of the OSEL encode output) so decode steps feed the
    grouped kernel with zero per-call W gathers. Defaults to on
    whenever ``params`` is given; pass ``False`` for a layout-only
    PlanState (e.g. to measure the per-call-gather path). The attached weights
    snapshot this params version — re-attach at params boundaries
    (:func:`refresh_cache_plans` does, even when the layout signature
    certifies).

    ``per_slot``: allocate ``cache["pos"]`` as a (batch,) vector instead
    of a scalar — each batch row becomes an independent request *slot* at
    its own stream offset. This is the continuous-batching layout
    (``repro.serving``): requests join and leave the decode batch
    mid-flight, and :func:`reset_slots` recycles a freed row for a fresh
    request. The lockstep scalar layout stays the default.
    """
    dtype = dtype or cfg.dtype
    nb = cfg.n_blocks
    blocks = {}
    for i, slot in enumerate(cfg.pattern):
        if slot.mixer == "attn":
            kv = (nb, batch, _cache_len(slot, max_seq), cfg.n_kv_heads,
                  cfg.head_dim)
            blocks[f"slot{i}"] = {
                "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
        else:
            conv_ch = cfg.d_inner + 2 * cfg.ssm_state
            blocks[f"slot{i}"] = {
                "state": jnp.zeros((nb, batch, cfg.ssm_heads,
                                    cfg.ssm_head_dim, cfg.ssm_state),
                                   jnp.float32),
                "conv": jnp.zeros((nb, batch, cfg.conv_width - 1, conv_ch),
                                  dtype)}
    pos_shape = (batch,) if per_slot else ()
    cache = {"pos": jnp.zeros(pos_shape, jnp.int32), "blocks": blocks}
    plans = ()
    if params is not None:
        state = encode_plans(params, cfg)
        if state.plans:               # grouped path: PlanState beside the KV
            if compact is None or compact:
                state = planenc.attach_compact(state, params)
            plans = state
    cache["plans"] = plans
    if cfg.encoder_layers:
        cache["encoder_out"] = jnp.zeros(
            (batch, cfg.num_frames, cfg.d_model), dtype)
    return cache


def refresh_cache_plans(params, cfg: ModelConfig, cache: dict) -> dict:
    """Request-boundary staleness check for the serving PlanState.

    ``cache["plans"]`` is encoded once (``init_cache(..., params=...)``)
    and trusted by every decode step — correct while params are frozen,
    stale the moment online tuning moves them between requests. Call this
    at the prefill/serve boundary of each request: it re-hashes the
    current params' grouping layout (:func:`repro.core.encoder.
    plan_signature`) against the cached signature and re-encodes only on
    a mismatch, so the per-request cost is ~half an encode when nothing
    moved and exactly one encode when it did. Caches without a PlanState
    (off the grouped path) pass through untouched. Jit-friendly — compose
    it into a request-setup step or call it eagerly between requests.
    """
    plans = cache.get("plans")
    if not isinstance(plans, planenc.PlanState) or not plans.plans:
        return cache
    fresh = planenc.refresh_if_stale(
        params, plans, encode=lambda: encode_plans(params, cfg))
    return dict(cache, plans=fresh)


def reset_slots(cache: dict, mask) -> dict:
    """Recycle batch rows of a per-slot decode cache for fresh requests.

    ``mask``: (batch,) bool — True rows are cleared: their stream offset
    returns to 0 and their SSM recurrent/conv state zeroes (it integrates
    every step, so the previous occupant would leak into the newcomer).
    KV buffers need no clearing — resetting ``pos`` invalidates every ring
    index (each maps to a negative absolute position until rewritten), and
    masked logits contribute exactly 0 after the softmax. False rows pass
    through bitwise-untouched (the slot-isolation contract, pinned in
    tests/test_scheduler.py). Requires a ``per_slot=True`` cache;
    jit-friendly.
    """
    pos = cache["pos"]
    if jnp.ndim(pos) != 1:
        raise ValueError(
            "reset_slots needs a per-slot cache (init_cache(per_slot=True)); "
            "this cache has a scalar shared position")
    mask = jnp.asarray(mask, bool)
    out = dict(cache, pos=jnp.where(mask, 0, pos))
    blocks = {}
    for name, c in cache["blocks"].items():
        nc = dict(c)
        for leaf in ("state", "conv"):
            if leaf in c:
                m = mask.reshape((1, -1) + (1,) * (c[leaf].ndim - 2))
                nc[leaf] = jnp.where(m, jnp.zeros((), c[leaf].dtype), c[leaf])
        blocks[name] = nc
    out["blocks"] = blocks
    return out


def plan_specs(cfg: ModelConfig, *, compact: bool = False):
    """Logical spec tree of the stack's cached PlanState (replicated: the
    compact metadata is small int/bool tensors consumed whole by every
    shard). ``()`` off the grouped path — matching ``init_cache`` /
    ``TrainState.plans``. ``compact=True`` mirrors a weight-attached
    state (``init_cache(params=...)``'s default), whose ``wc`` leaves are
    likewise replicated."""
    if cfg.flgw_groups <= 1 or cfg.flgw_path != "grouped":
        return ()

    def _abstract(k):
        state = encode_plans(lm_init(k, cfg)[0], cfg)
        if compact:
            state = planenc.attach_compact(state, lm_init(k, cfg)[0])
        return state
    aplans = jax.eval_shape(_abstract, jax.random.PRNGKey(0))
    return jax.tree.map(lambda a: (None,) * a.ndim, aplans)


def cache_specs(cfg: ModelConfig, *, per_slot: bool = False) -> dict:
    """Logical-axis spec tree mirroring ``init_cache``.

    KV is sharded over the *sequence* dim on the model axis ("seq_kv") —
    sequence length is always large and divisible, unlike GQA KV head
    counts (4–16), and batch=1 long-context cells can't use the data axis.
    This is the flash-decoding-style layout: each model shard scores its
    slice of the KV cache and the tiny (B, H, hd) partial results reduce.
    """
    blocks = {}
    for i, slot in enumerate(cfg.pattern):
        if slot.mixer == "attn":
            kv = ("layers", "batch", "seq_kv", "kv_heads", None)
            blocks[f"slot{i}"] = {"k": kv, "v": kv}
        else:
            blocks[f"slot{i}"] = {
                "state": ("layers", "batch", "heads", None, None),
                "conv": ("layers", "batch", None, "ffn")}
    specs = {"pos": ("batch",) if per_slot else (), "blocks": blocks,
             "plans": plan_specs(cfg, compact=True)}
    if cfg.encoder_layers:
        specs["encoder_out"] = ("batch", None, None)
    return specs
