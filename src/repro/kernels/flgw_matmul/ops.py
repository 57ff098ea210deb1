"""Jit'd public wrapper around the FLGW grouped-matmul Pallas kernel.

Pipeline (the TPU analogue of LearningGroup's load-allocation unit + cores):

  1. gather   x  -> x_c  (G, B, capM)    activations per group
  2. gather   W  -> W_c  (G, capM, capN) unmasked weights only (÷G bytes)
  3. Pallas   y_c = x_c @ W_c            MXU block-diagonal matmul (÷G FLOPs)
  4. scatter  y_c -> y   (B, N)          compact outputs to dense columns

The gathers/scatter are memory-bound VPU work handled by XLA; the matmul is
the Pallas kernel. On non-TPU backends the kernel runs in interpret mode.

Step 2 is skipped when the caller passes ``wc``: the compact weights from
:func:`compact_weights`, cached beside the plan for the life of a params
version (the paper's OSEL→core handoff) — the serving consume path.
``impl="reference"`` lowers the jnp reference instead (GSPMD-shardable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flgw_matmul.flgw_matmul import grouped_bmm
from repro.kernels.flgw_matmul import ref as _ref

# Reference-impl mode: under plain jit, GSPMD cannot partition a pallas
# custom call — it replicates the kernel computation on every chip (the
# gemma2-2b dry-run measured 28x compute). On real TPUs the kernel is
# invoked under shard_map on local blocks; for the CPU dry-run we lower the
# mathematically identical jnp reference instead, which GSPMD shards like
# any einsum. The switch now lives in ``repro.kernels`` (shared with the
# plan_encode kernel); these aliases keep existing callers working.
from repro.kernels import _REF_MODE, use_reference_impl  # noqa: F401
# Tile arithmetic is shared with the static auditor
# (repro.kernels.flgw_matmul.audit) so the audited grid is, by
# construction, the grid this wrapper builds.
from repro.kernels.tiling import pick_tile as _pick_tile
from repro.kernels.tiling import round_up as _round_up


@functools.partial(jax.jit, static_argnames=("interpret", "impl"))
def grouped_matmul(x: jax.Array, w: jax.Array, row_ids: jax.Array,
                   col_ids: jax.Array, row_valid: jax.Array,
                   col_valid: jax.Array, wc: jax.Array | None = None, *,
                   interpret: bool | None = None,
                   impl: str = "pallas") -> jax.Array:
    """Compact FLGW matmul. Shapes: x (B, M), w (M, N), row_ids (G, capM),
    col_ids (G, capN); returns y (B, N). See ref.ref_grouped_matmul.

    ``wc``: the cached ``(G, capM, capN)`` compact weights
    (:func:`compact_weights` of this ``w`` and plan); when given, the
    per-call weight gather is skipped. ``impl="reference"`` lowers the jnp
    reference instead of the Pallas kernel (GSPMD-shardable; see
    use_reference_impl) and reads ``w`` only."""
    if impl == "reference" or _REF_MODE:
        return _ref.ref_grouped_matmul(x, w, row_ids, col_ids, row_valid,
                                       col_valid)
    if interpret is None:
        interpret = default_interpret()
    b, m = x.shape
    n = w.shape[1]
    g, cap_m = row_ids.shape
    cap_n = col_ids.shape[1]

    # --- gathers -----------------------------------------------------------
    xg = jnp.take(x, row_ids.reshape(-1), axis=1)
    xg = xg.reshape(b, g, cap_m).transpose(1, 0, 2)          # (G, B, capM)
    xg = jnp.where(row_valid[:, None, :], xg, 0)
    if wc is None:
        wc = compact_weights(w, row_ids, col_ids, row_valid, col_valid)
    assert wc.shape == (g, cap_m, cap_n), (wc.shape, row_ids.shape,
                                           col_ids.shape)

    # --- pad to tile multiples for the kernel ------------------------------
    bb = _pick_tile(b, 128)
    bn = _pick_tile(cap_n, 128)
    bk = _pick_tile(cap_m, 128)
    bp, mp, np_ = _round_up(b, bb), _round_up(cap_m, bk), _round_up(cap_n, bn)
    xg = jnp.pad(xg, ((0, 0), (0, bp - b), (0, mp - cap_m)))
    wc = jnp.pad(wc, ((0, 0), (0, mp - cap_m), (0, np_ - cap_n)))

    yc = grouped_bmm(xg, wc, bb=bb, bn=bn, bk=bk, interpret=interpret)
    yc = yc[:, :b, :cap_n]                                   # (G, B, capN)

    # --- scatter back to dense column order --------------------------------
    flat_cols = jnp.where(col_valid, col_ids, n).reshape(-1)
    yt = yc.transpose(1, 0, 2).reshape(b, -1)
    return jnp.zeros((b, n), x.dtype).at[:, flat_cols].set(yt, mode="drop")


def compact_weights(w: jax.Array, row_ids: jax.Array, col_ids: jax.Array,
                    row_valid: jax.Array, col_valid: jax.Array) -> jax.Array:
    """``W -> W_c`` (G, capM, capN): the weight half of the encode output.

    This is the paper's OSEL handoff — the dense weight compacted into the
    ``(G, cap)`` format the cores consume directly. Invalid slots are
    zeroed, so a cached ``W_c`` is exactly what :func:`grouped_matmul`
    would gather per call. Handles stacked leading dims (scanned decoder
    layers, vmapped experts) by folding them into a vmap.
    """
    if w.ndim > 2:
        return jax.vmap(compact_weights)(w, row_ids, col_ids, row_valid,
                                         col_valid)
    wc = w[row_ids[:, :, None], col_ids[:, None, :]]         # (G, capM, capN)
    return jnp.where(row_valid[:, :, None] & col_valid[:, None, :], wc, 0)


def reference(x, w, row_ids, col_ids, row_valid, col_valid):
    return _ref.ref_grouped_matmul(x, w, row_ids, col_ids, row_valid,
                                   col_valid)
