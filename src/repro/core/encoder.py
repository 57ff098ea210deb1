"""Device-resident plan-encoder subsystem — one OSEL analogue for all stacks.

The paper's OSEL encodes the FLGW mask *once per iteration* into compact
sparse metadata the whole step reuses (§III-B). This module is that
encoder as a first-class subsystem shared by every workload (the MARL
engine and the LM/transformer stack), instead of per-caller helpers:

* :class:`PlanState` — the cached metadata: one :class:`~repro.core.grouped.
  GroupPlan` per FLGW-carrying projection (nested dict mirroring the param
  tree; stacked/scanned layers get stacked plans) plus a ``sig`` hash of
  the ig/og argmaxes the plans were encoded from.
* :func:`encode_plans` — one encoding pass over any param tree. The
  balanced assignment itself runs on the ``plan_encode`` Pallas kernel
  (``repro.kernels.plan_encode``).
* :func:`maybe_refresh` — the refresh policy, usable under trace
  (``lax.cond`` inside) and from host loops alike:

  - ``"period"``    — re-encode every ``schedule.refresh_every`` steps
    (the PR-2 behavior; the paper's once-per-iteration encode at k=1);
  - ``"on_change"`` — re-encode only when the balanced-deal layout
    actually moved (detected via ``sig``, which hashes the ig/og argmaxes
    *and* the within-group confidence ranks — so ``slack > 1`` spill-order
    drift fires a refresh too, not just argmax flips). The paper's masks
    churn early and freeze late, so change-driven refresh matches per-step
    re-encoding exactly while masks move and costs one signature pass —
    one sort + a segmented count per side, ~half an encode — once they
    freeze. Exactness frontier; a coarse ``"period"`` buys more
    throughput with the staleness it tolerates (fig12);
  - ``"hybrid"``    — on change, with ``refresh_every`` as a staleness
    bound (belt-and-suspenders against hash collisions; before the
    signature hashed placement ranks it was the only mode that bounded
    spill-order staleness).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core import grouped

REFRESH_MODES = ("period", "on_change", "hybrid")

_MIX = 2654435761        # Knuth's multiplicative-hash constant (odd)
_FOLD = 1000003          # layer-fold multiplier (odd)


class PlanState(NamedTuple):
    """Cached sparse metadata of a param tree + the hash it was built from.

    ``plans`` mirrors the params nesting with a GroupPlan at every
    FLGW-carrying projection (``{}`` when the grouped path is off — the
    empty state keeps training-loop carries structurally uniform).
    ``sig`` is a uint32 hash of the grouping layout (:func:`plan_signature`):
    any single argmax flip — and any within-group confidence reorder, which
    moves slots/spills under ``slack > 1`` — changes it, so ``sig``
    equality certifies the cached plans are still bitwise-identical to a
    fresh encode of the current grouping matrices.
    """
    plans: Any
    sig: jax.Array

    def __bool__(self) -> bool:           # truthiness == "has any plans"
        return bool(self.plans)


def empty_state() -> PlanState:
    return PlanState({}, jnp.zeros((), jnp.uint32))


def _layout_ranks(scores: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(pref, rank) of one grouping side; ``scores``: (..., M, G).

    ``pref`` is each item's argmax group; ``rank`` is the item's position
    *within its preferred group* under the (strength desc, index asc)
    order — together they determine the balanced deal's placement order
    (pref asc, strength desc, index asc; see ``plan_encode.ref``) and
    therefore the compact layout bitwise: a strength reorder inside one
    group permutes slots and redirects which overflow item spills
    (``slack > 1``), even when no argmax flips.

    Cost matters — on_change evaluates this every step, so it must stay
    well under one encode: one stable argsort, a segmented count via
    cumsum (O(M·G)), and a scatter back to item order — cheaper than the
    encode's own lexsort-equivalent two-sort pipeline.
    """
    g = scores.shape[-1]
    pref = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    strength = jnp.max(scores, axis=-1)
    order = jnp.argsort(-strength, axis=-1, stable=True)   # ties: index asc
    pref_sorted = jnp.take_along_axis(pref, order, axis=-1)
    # Within-group rank of each sorted position: running count of earlier
    # same-group items in strength order.
    cnt = jnp.cumsum(jax.nn.one_hot(pref_sorted, g, dtype=jnp.int32),
                     axis=-2)
    rank_sorted = jnp.take_along_axis(
        cnt, pref_sorted[..., None], axis=-1)[..., 0] - 1
    rank = jnp.put_along_axis(jnp.zeros_like(rank_sorted), order,
                              rank_sorted, axis=-1, inplace=False)
    return pref, rank


def plan_signature(params: dict) -> jax.Array:
    """uint32 hash of every FLGW layer's balanced-deal layout.

    Hashes, per layer and grouping side, the argmax index vector *and*
    the placement-rank vector (:func:`_layout_ranks`), so the signature
    changes iff a fresh encode would produce a bitwise-different plan —
    argmax flips and ``slack > 1`` spill-order drift alike. Each value
    gets an odd per-position weight and layers fold with an odd
    multiplier, so any single change moves the hash
    (odd · nonzero ≠ 0 mod 2^32); simultaneous multi-change cancellation
    is the only collision mode and is vanishingly unlikely.
    """
    h = jnp.zeros((), jnp.uint32)
    salt = 1
    for _, p in grouped.iter_flgw_layers(params):
        for scores in (p["ig"], jnp.swapaxes(p["og"], -1, -2)):
            for idx in _layout_ranks(scores):
                v = idx.astype(jnp.uint32).reshape(-1)
                w = (jnp.arange(v.shape[0], dtype=jnp.uint32)
                     * jnp.uint32(_MIX) + jnp.uint32(salt)) | jnp.uint32(1)
                h = h * jnp.uint32(_FOLD) + jnp.sum((v + jnp.uint32(1)) * w)
                salt += 2
    return h


def encode_plans(params: dict, cfg) -> PlanState:
    """One encoding pass over a param tree — plans + their signature.

    ``cfg`` is the layer's :class:`~repro.core.flgw.FLGWConfig` (anything
    with ``capacity_slack``). Handles flat trees (MARL/IC3Net) and stacked
    scan-layer trees (the LM decoder) alike — see
    :func:`repro.core.grouped.encode_plans` for the per-layer walk.
    """
    return PlanState(grouped.encode_plans(params, cfg),
                     plan_signature(params))


def attach_compact(state: PlanState, params: dict) -> PlanState:
    """Attach compact weights (``GroupPlan.wc``) to every plan in a state.

    The serving-side half of the OSEL handoff: gather once per params
    version, consume it in every grouped kernel call until the params move. The
    signature is layout-only — it does *not* certify ``wc`` — so holders
    of an attached state must re-attach at every params boundary (the
    refresh hooks below do this automatically) and must never share the
    attached state across params versions (e.g. through the process-wide
    plan cache, which is keyed by layout signature alone).
    """
    if not isinstance(state, PlanState) or not state.plans:
        return state
    return state._replace(plans=grouped.attach_compact(state.plans, params))


def _certify(state: PlanState, params: dict) -> PlanState:
    """The pass-through branch of a refresh: layout certified by ``sig``,
    but any attached ``wc`` snapshots weight *values*, which the
    signature deliberately ignores — re-gather them from the params being
    certified against so online param updates can never serve stale
    weights through a layout-stable plan."""
    if grouped.has_compact(state.plans):
        return attach_compact(state, params)
    return state


def maybe_refresh(params: dict, state: PlanState, it, cfg,
                  schedule=None) -> PlanState:
    """Re-encode ``state`` from the current grouping matrices when due.

    ``it`` may be a traced int32 (``lax.cond`` inside) — the same function
    serves the on-device ``lax.scan`` carry, the mesh path and the host
    loop mirror. ``schedule`` is a ``SparsitySchedule`` (or None: refresh
    every step); its ``refresh`` field picks the policy. Empty states pass
    through untouched. ``state`` must be a :class:`PlanState` — a raw
    plans dict has no signature to compare, so the change-driven modes
    could never fire on one (wrap it via :func:`encode_plans` instead).
    """
    if not isinstance(state, PlanState):
        raise TypeError(
            f"maybe_refresh needs a PlanState, got {type(state).__name__}; "
            "build one with encoder.encode_plans")
    if not state.plans:
        return state
    mode = "period" if schedule is None else \
        getattr(schedule, "refresh", "period")
    if mode not in REFRESH_MODES:
        raise ValueError(f"unknown refresh mode {mode!r}")
    k = 1 if schedule is None else max(1, schedule.refresh_every)
    attached = grouped.has_compact(state.plans)
    fresh = (lambda: attach_compact(encode_plans(params, cfg), params)) \
        if attached else (lambda: encode_plans(params, cfg))
    with jax.named_scope(scopes.PLAN_REFRESH):
        if mode == "period" and k == 1:
            return fresh()
        due = jnp.asarray(it, jnp.int32) % k == 0
        if mode == "period":
            pred = due
        else:
            changed = plan_signature(params) != state.sig
            pred = changed if mode == "on_change" else changed | due
        return jax.lax.cond(pred, fresh, lambda: _certify(state, params))


def refresh_if_stale(params: dict, state: PlanState, cfg=None, *,
                     encode=None) -> PlanState:
    """Signature-gated re-encode with no step counter — the serving hook.

    :func:`maybe_refresh` assumes a training loop with an iteration
    counter; serving has none. Params are frozen *within* a request but
    may move *between* requests (online tuning), so the request boundary
    — prefill, or a cache reused across requests — must certify the
    cached plans against the *current* params instead of trusting them
    unconditionally. One :func:`plan_signature` pass (~half an encode)
    does that; a bitwise-different layout triggers exactly one re-encode,
    an unchanged layout passes the cached state through untouched.

    ``encode`` overrides the default ``encode_plans(params, cfg)`` for
    stacks with their own encode entry point (the transformer passes its
    ``ModelConfig``-aware encoder). Empty states pass through untouched.
    Traceable: ``lax.cond`` inside, so serve/prefill steps can jit it.
    """
    if not isinstance(state, PlanState):
        raise TypeError(
            f"refresh_if_stale needs a PlanState, got {type(state).__name__};"
            " build one with encoder.encode_plans")
    if not state.plans:
        return state
    if encode is None:
        if cfg is None:
            raise ValueError("refresh_if_stale needs cfg (or encode=)")
        encode = lambda: encode_plans(params, cfg)   # noqa: E731
    if grouped.has_compact(state.plans):
        # Attached compact weights: make the encode branch structurally
        # match, and re-gather wc even on the certified branch — sig is
        # layout-only, it cannot vouch for weight values (online tuning
        # may move W without moving the layout).
        base = encode
        encode = lambda: attach_compact(base(), params)   # noqa: E731
    sig = plan_signature(params)
    # Reuse the signature just computed instead of the one ``encode``
    # re-derives internally (identical by construction — same params):
    # under jit the duplicate inside the branch is then dead code, so a
    # refresh costs one signature + one encode, not two signatures.
    return jax.lax.cond(sig != state.sig,
                        lambda: encode()._replace(sig=sig),
                        lambda: _certify(state, params))


# re-export: the single source of truth for walking FLGW structure
iter_flgw_layers = grouped.iter_flgw_layers
