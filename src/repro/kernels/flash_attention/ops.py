"""Differentiable wrapper for the flash attention Pallas kernels.

``flash_attention(q, k, v, ...)`` is a drop-in fused replacement for the
materialized-logits attention core: custom_vjp wires the dq/dkv backward
kernels, so neither forward nor backward ever stores an (S, T) tensor.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import default_interpret
from repro.kernels.flash_attention.flash_attention import (flash_bwd,
                                                           flash_fwd)
from repro.kernels.flash_attention import ref as _ref
# Block selection is shared with the static auditor
# (repro.kernels.flash_attention.audit) so the audited grid is, by
# construction, the grid this wrapper builds.
from repro.kernels.tiling import pick_block as _pick_block


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    scale=None, bq=512, bk=512, interpret=None):
    """q: (B, Hq, S, D); k/v: (B, Hkv, T, D) -> (B, Hq, S, D)."""
    out, _ = _fwd(q, k, v, causal, window, softcap, scale, bq, bk,
                  interpret)
    return out


def _fwd(q, k, v, causal, window, softcap, scale, bq, bk, interpret):
    if interpret is None:
        interpret = default_interpret()
    bq = _pick_block(q.shape[2], bq)
    bk = _pick_block(k.shape[2], bk)
    return flash_fwd(q, k, v, causal=causal, window=window,
                     softcap=softcap, scale=scale, bq=bq, bk=bk,
                     interpret=interpret)


def _flash_fwd_rule(q, k, v, causal, window, softcap, scale, bq, bk,
                    interpret):
    out, lse = _fwd(q, k, v, causal, window, softcap, scale, bq, bk,
                    interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, softcap, scale, bq, bk, interpret,
                    res, do):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = default_interpret()
    bq_ = _pick_block(q.shape[2], bq)
    bk_ = _pick_block(k.shape[2], bk)
    dq, dk, dv = flash_bwd(q, k, v, out, lse, do, causal=causal,
                           window=window, softcap=softcap, scale=scale,
                           bq=bq_, bk=bk_, interpret=interpret)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def reference(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None):
    return _ref.ref_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale)
