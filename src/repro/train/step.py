"""train_step factory — the function the launcher jits.

``make_train_step(cfg)`` returns a pure ``(state, batch) -> (state, metrics)``
step: forward (remat-scanned blocks, chunked CE), backward, optional
microbatch gradient accumulation (scan), global-norm clip, optimizer update.

The serving factories that used to live here (``make_serve_step`` /
``make_prefill_step``) moved to ``repro.serving.steps`` behind the unified
:class:`repro.serving.ServeSession` API; the deprecation shims that bridged
the move are gone — use ``repro.serving.make_decode_step`` /
``repro.serving.make_prefill_step`` (old ``make_serve_step(...,
refresh_plans=True)`` maps to ``make_decode_step(...,
certify_each_step=True)``).

Everything is shape-static: the dry-run lowers these exact functions against
ShapeDtypeStructs, and the real launcher jits them with the same shardings.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core import encoder as planenc
from repro.core.flgw import FLGWConfig
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim.optimizers import adamw, clip_by_global_norm, rmsprop
from repro.train.loss import chunked_cross_entropy
from repro.train.state import TrainState


def pick_q_chunk(s: int, pref: int = 512) -> int:
    """Largest divisor of ``s`` that is ≤ pref and a multiple of 128 (or s)."""
    if s <= pref:
        return s
    for c in range(pref, 127, -128):
        if s % c == 0:
            return c
    for c in range(pref, 0, -1):
        if s % c == 0:
            return c
    return s


def _loss_fn(params, batch, cfg: ModelConfig, q_chunk: int, banded: bool,
             ce_chunk: int = 512, ssd_unroll: bool = False,
             unroll_blocks: bool = False, attn_identity: bool = False,
             plans=None):
    hidden, aux, _ = transformer.lm_apply(
        params, cfg, batch["tokens"], batch["positions"],
        patch_embeds=batch.get("patch_embeds"),
        frames=batch.get("frames"),
        q_chunk=q_chunk, banded=banded, return_hidden=True,
        ssd_unroll=ssd_unroll, unroll_blocks=unroll_blocks,
        attn_identity=attn_identity, plans=plans)
    with jax.named_scope(scopes.LM_HEAD):
        ce = chunked_cross_entropy(
            hidden, transformer.head_weight(params, cfg), batch["targets"],
            logit_softcap=cfg.logit_softcap, chunk=ce_chunk)
    metrics = {"ce": ce, "aux": aux["balance"]}
    if "routed" in aux:
        metrics.update(moe_routed=aux["routed"], moe_peak=aux["peak"])
    return ce + cfg.aux_weight * aux["balance"], metrics


def make_train_step(cfg: ModelConfig, *, optimizer: str = "adamw",
                    lr: float = 3e-4, clip: float = 1.0,
                    microbatches: int = 1, banded: bool = False,
                    q_chunk: Optional[int] = None, ce_chunk: int = 512,
                    ssd_unroll: bool = False, unroll_blocks: bool = False,
                    attn_identity: bool = False, schedule=None):
    """Returns ``train_step(state, batch) -> (state, metrics)``.

    ``q_chunk`` / ``ce_chunk`` / ``ssd_unroll`` exist for the dry-run cost
    variant (scan-free lowering so HLO cost analysis sees every op); the
    real launcher uses the memory-bounded defaults.

    On the FLGW grouped path the step drives the same plan-refresh logic
    as the MARL engine: ``state.plans`` (the cached PlanState built at
    ``init_state``) passes through ``encoder.maybe_refresh`` against the
    ``schedule``'s refresh mode before the forward, so every projection
    consumes cached metadata instead of re-encoding per call, and the
    (possibly re-encoded) plans carry into the next state.
    """
    uses_plans = cfg.flgw_groups > 1 and cfg.flgw_path == "grouped"
    fl_cfg = FLGWConfig(groups=cfg.flgw_groups, path=cfg.flgw_path)

    def train_step(state: TrainState, batch):
        s = batch["tokens"].shape[1]
        qc = q_chunk or pick_q_chunk(s)
        plans = state.plans
        if uses_plans and isinstance(plans, planenc.PlanState):
            plans = planenc.maybe_refresh(state.params, plans, state.step,
                                          fl_cfg, schedule)
        grad_fn = jax.value_and_grad(
            functools.partial(_loss_fn, cfg=cfg, q_chunk=qc, banded=banded,
                              ce_chunk=ce_chunk, ssd_unroll=ssd_unroll,
                              unroll_blocks=unroll_blocks,
                              attn_identity=attn_identity,
                              plans=plans if uses_plans else None),
            has_aux=True)

        if microbatches == 1:
            (loss, metrics), grads = grad_fn(state.params, batch)
        else:
            def split(x):
                return x.reshape(microbatches, x.shape[0] // microbatches,
                                 *x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc(carry, b_i):
                g_acc, l_acc = carry
                (l, _), g = grad_fn(state.params, b_i)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            (grads, loss), _ = jax.lax.scan(
                acc, (zeros, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}

        grads, gnorm = clip_by_global_norm(grads, clip)
        if optimizer == "adamw":
            params, opt = adamw(state.params, grads, state.opt, lr=lr)
        else:
            params, opt = rmsprop(state.params, grads, state.opt, lr=lr)
        new_state = TrainState(params=params, opt=opt, step=state.step + 1,
                               plans=plans)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return new_state, metrics

    return train_step
