"""ServeSession — the one serving surface.

Before this module the serving API was scattered kwargs across three
modules: ``make_serve_step(refresh_plans=...)``, ``make_prefill_step(
plans=...)``, ``transformer.init_cache(params=...)`` and
``transformer.refresh_cache_plans``. A :class:`ServeSession` owns all of
it: the params version being served, the jitted prefill/decode steps, the
cache factory for both layouts (lockstep scalar-``pos`` and per-slot),
and one explicit ``plan_policy`` knob governing every plan-cache decision
— both the continuous-batching scheduler (``repro.serving.scheduler``)
and the lockstep path build on it. (The ``repro.train.step`` deprecation
shims that bridged the move are retired.)

Plan resolution goes through the process-wide cache
(``repro.serving.plan_cache``): concurrent sessions and requests against
the same params version share one certified PlanState — encode once per
params version, fan out to every in-flight request (the paper's
OSEL→core dataflow, at serving scope).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import encoder as planenc
from repro.core.flgw import FLGWConfig
from repro.models import transformer
from repro.serving import plan_cache
from repro.serving.steps import (check_plan_policy, make_decode_step,
                                 make_prefill_step)


class ServeSession:
    """One params version being served, with its plans and jitted steps.

    ``plan_policy``:

    * ``"certify"`` (default) — plans resolve through the process-wide
      plan cache at every request boundary (:meth:`refresh`,
      :meth:`update_params`, scheduler admission): one signature pass per
      boundary, a re-encode only when the grouping layout actually moved,
      and at most one encode per params version process-wide no matter
      how many concurrent consumers share it.
    * ``"trust"`` — plans are resolved once (here, and again at explicit
      :meth:`update_params` calls) and consumed unconditionally in
      between: zero signature work on the hot path. The caller promises
      params never move without an ``update_params``.
    * ``"off"`` — no cached plans: every grouped projection re-encodes
      per call. The unamortized baseline (and a no-op off the grouped
      path, where there are no plans to cache).
    """

    def __init__(self, cfg, params, *, plan_policy: str = "certify",
                 banded: bool = False, unroll_blocks: bool = False,
                 share_plans: bool = True, jit: bool = True,
                 debug_contracts: bool = False):
        self.cfg = cfg
        self.params = params
        self.plan_policy = check_plan_policy(plan_policy)
        # opt-in trace/compile contract (repro.analysis.contracts):
        # engines built on this session run their tick loop under
        # no_retrace — one compile per jitted step per shape, ever
        self.debug_contracts = debug_contracts
        self._share = share_plans
        self._grouped = cfg.flgw_groups > 1 and cfg.flgw_path == "grouped"
        self._slack = FLGWConfig(groups=cfg.flgw_groups,
                                 path=cfg.flgw_path).capacity_slack
        decode = make_decode_step(cfg, banded=banded,
                                  unroll_blocks=unroll_blocks)
        prefill = make_prefill_step(cfg, plan_policy=plan_policy,
                                    banded=banded)
        self._decode = jax.jit(decode) if jit else decode
        self._prefill = jax.jit(prefill) if jit else prefill
        self._wc_memo = None
        self.plans = self._resolve_plans()

    # -- plan resolution ---------------------------------------------------

    def _resolve_plans(self):
        """The session's PlanState under the current params — through the
        process-wide cache (one encode per params version) unless sharing
        is off; ``()`` under ``plan_policy="off"`` or off the grouped
        path (matching ``init_cache`` without params).

        The resolved state is *layout-only* — the shared cache is keyed
        by the layout signature, which never hashes weight values, so
        weight-bearing states must not live there (or in ``self.plans``,
        which concurrent sessions share by identity). The compact weights
        (``GroupPlan.wc``, the grouped kernel's cached weight operand) are attached
        session-locally at the consumption points (:meth:`new_cache`,
        :meth:`refresh`, :meth:`prefill`) via :meth:`_attach`."""
        if self.plan_policy == "off" or not self._grouped:
            return ()
        encode = lambda: transformer.encode_plans(self.params, self.cfg)  # noqa: E731
        if not self._share:
            return encode()
        return plan_cache.shared_plans(self.params, encode=encode,
                                       slack=self._slack)

    def _attach(self, state):
        """Session-local OSEL handoff: this session's params compacted
        onto the shared layout (``GroupPlan.wc``), memoized so an
        unchanged (plans, params) pair costs zero re-gathers at request
        boundaries. Never mutates or replaces the shared ``state``."""
        if not state:
            return state
        memo = self._wc_memo
        if memo and memo[0] is state and memo[1] is self.params:
            return memo[2]
        attached = planenc.attach_compact(state, self.params)
        self._wc_memo = (state, self.params, attached)
        return attached

    def update_params(self, params) -> None:
        """Publish a new params version to the session (online tuning).

        The explicit boundary for every policy: ``certify`` and ``trust``
        both re-resolve the PlanState here (through the shared cache, so
        a version other sessions already serve costs one signature pass,
        zero encodes). Caches handed out earlier still hold the old
        PlanState — pass them through :meth:`refresh` (certify) or
        rebuild them (trust).
        """
        self.params = params
        self.plans = self._resolve_plans()

    def refresh(self, cache: dict) -> dict:
        """Request-boundary certification of a cache's PlanState.

        Under ``certify``, re-resolves the plans against the session's
        current params and swaps them into the cache (signature pass per
        call; encode only on a genuinely new layout). Under ``trust`` and
        ``off`` this is a no-op — that is the policy's meaning.
        """
        if self.plan_policy != "certify" or not self._grouped:
            return cache
        if not isinstance(cache.get("plans"), planenc.PlanState):
            return cache
        self.plans = self._resolve_plans()
        return dict(cache, plans=self._attach(self.plans))

    # -- caches ------------------------------------------------------------

    def new_cache(self, batch: int, max_seq: int, dtype=None, *,
                  per_slot: bool = False) -> dict:
        """Decode cache carrying the session's plans per ``plan_policy``.

        ``per_slot=True`` allocates the continuous-batching layout (one
        stream offset per batch row — see ``transformer.init_cache``).
        """
        cache = transformer.init_cache(self.cfg, batch, max_seq, dtype,
                                       per_slot=per_slot)
        cache["plans"] = self._attach(self.plans) if self._grouped and \
            self.plan_policy != "off" else ()
        return cache

    # -- steps -------------------------------------------------------------

    def decode(self, cache: dict, tokens, positions):
        """One greedy decode step: ``(next_tok, cache)``."""
        return self._decode(self.params, cache, tokens, positions)

    def prefill(self, batch, plans=...):
        """Full-sequence prefill -> last-position logits. ``plans``
        defaults to the session's PlanState (policy-resolved); pass
        explicitly (e.g. ``cache["plans"]``) to override."""
        if plans is ...:
            plans = self._attach(self.plans) if self._grouped and \
                self.plan_policy != "off" else None
        if plans == ():
            plans = None
        return self._prefill(self.params, batch, plans)

    def greedy_positions(self, batch: int, pos: int):
        """(batch, 1) positions column for a lockstep decode step."""
        return jnp.full((batch, 1), pos, jnp.int32)
