"""Serving step factories — the functions the serving tier jits.

These were previously scattered across ``repro.train.step``
(``make_serve_step`` / ``make_prefill_step`` — kept there as deprecated
shims) and ``repro.models.transformer`` (``init_cache(params=...)`` /
``refresh_cache_plans``). The consolidated surface is
:class:`repro.serving.session.ServeSession`; these factories are the
session's building blocks, exposed for callers that manage their own jit
boundary (the dry-run compiles them against abstract shardings).

The one policy knob is ``plan_policy`` (see :data:`PLAN_POLICIES`):

* ``"certify"`` — cached PlanStates are signature-checked at request
  boundaries and re-encoded iff the grouping layout moved (safe under
  online tuning; the default).
* ``"trust"``  — cached PlanStates are consumed unconditionally: zero
  signature work, caller promises params are frozen between explicit
  ``ServeSession.update_params`` calls.
* ``"off"``    — no plan caching anywhere: grouped projections re-encode
  per call (the unamortized fallback — mostly a measurement baseline).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import encoder as planenc
from repro.models import transformer

PLAN_POLICIES = ("certify", "trust", "off")


def check_plan_policy(plan_policy: str) -> str:
    if plan_policy not in PLAN_POLICIES:
        raise ValueError(
            f"plan_policy must be one of {PLAN_POLICIES}, got "
            f"{plan_policy!r}")
    return plan_policy


def make_decode_step(cfg, *, banded: bool = False,
                     unroll_blocks: bool = False,
                     certify_each_step: bool = False):
    """Returns ``decode_step(params, cache, tokens, positions)`` —
    one-token greedy decode against the KV/SSM caches.

    Works against both cache layouts: the lockstep scalar-``pos`` cache
    and the per-slot (``init_cache(per_slot=True)``) cache the
    continuous-batching scheduler drives, where every batch row holds its
    own stream offset and ``positions`` carries per-row values.

    On the FLGW grouped path the cache's PlanState (parked beside the
    KV/SSM buffers) is consumed by every projection — zero ``make_plan``
    work per step. ``certify_each_step=True`` builds a signature check
    into every step (the old ``make_serve_step(refresh_plans=True)``) —
    for servers that interleave tuning and decoding with no request
    boundary to hook; it costs ~half an encode per step, so request-level
    certification (``ServeSession.refresh`` / admission) is the default.
    """

    def decode_step(params, cache, tokens, positions):
        if certify_each_step:
            cache = transformer.refresh_cache_plans(params, cfg, cache)
        logits, _, cache = transformer.lm_apply(
            params, cfg, tokens, positions, cache=cache, banded=banded,
            remat=False, unroll_blocks=unroll_blocks)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return decode_step


def make_prefill_step(cfg, *, plan_policy: str = "certify",
                      banded: bool = False, q_chunk: Optional[int] = None,
                      ssd_unroll: bool = False, unroll_blocks: bool = False,
                      attn_identity: bool = False):
    """Returns ``prefill(params, batch, plans=None) -> last logits`` —
    the full-sequence forward of the prefill shape cells.

    Plan handling follows ``plan_policy``:

    * ``"certify"`` — a caller-supplied PlanState (e.g. the plans cached
      beside a KV cache) is certified against the current params: one
      signature pass, a re-encode iff the grouping layout moved. With no
      plans, encodes once for the whole forward.
    * ``"trust"``   — caller plans are consumed as-is (no signature work);
      with no plans, encodes once.
    * ``"off"``     — ignores caller plans; every grouped projection
      re-encodes per call.
    """
    check_plan_policy(plan_policy)
    from repro.train.step import pick_q_chunk

    def prefill_step(params, batch, plans=None):
        s = batch["tokens"].shape[1]
        qc = q_chunk or pick_q_chunk(s)
        if plan_policy == "off":
            plans = None
        elif plans is None:
            # empty PlanState (a no-op) off the grouped path
            plans = transformer.encode_plans(params, cfg)
        elif (plan_policy == "certify"
              and isinstance(plans, planenc.PlanState) and plans.plans):
            plans = planenc.refresh_if_stale(
                params, plans,
                encode=lambda: transformer.encode_plans(params, cfg))
        hidden, _, _ = transformer.lm_apply(
            params, cfg, batch["tokens"], batch["positions"],
            patch_embeds=batch.get("patch_embeds"),
            frames=batch.get("frames"),
            q_chunk=qc, banded=banded, remat=False, return_hidden=True,
            ssd_unroll=ssd_unroll, unroll_blocks=unroll_blocks,
            moe_dropless=True, attn_identity=attn_identity, plans=plans)
        # Only the last position's logits are needed to start decoding.
        from repro.models.layers import softcap, unembed
        logits = unembed(transformer.head_weight(params, cfg), hidden[:, -1:])
        return softcap(logits.astype(jnp.float32), cfg.logit_softcap)

    return prefill_step
