"""Pallas TPU flash attention: fused online-softmax attention, fwd + bwd.

The dry-run roofline shows every attention cell is MEMORY-bound because the
(S, T) logit matrix materializes in HBM (write + multi-pass softmax reads,
then again under remat). This kernel keeps the logits in VMEM tiles and
streams K/V blocks through the MXU — the standard TPU adaptation of
FlashAttention, extended with the features our architectures need:

  * GQA: q-head h reads kv-head h // qpk via the BlockSpec index map —
    no materialized KV repeat.
  * causal + sliding-window masking by absolute position, with whole-block
    skipping (a fully-masked (bq, bk) tile never touches the MXU);
  * gemma-style attention-logit softcap (tanh), handled exactly in bwd;
  * the operands' dtype on the MXU (bf16 products for bf16 inputs, the
    probabilities and logit gradients cast to it) with f32 accumulation;
  * a value head narrower than the query/key head (DeepSeek's MLA:
    192-wide q/k, 128-wide v).

Layouts: q (B, Hq, S, D), k (B, Hkv, T, D), v (B, Hkv, T, Dv), out
(B, Hq, S, Dv); the per-row statistics lse/delta are (B, Hq, S, 1).
Backward is the standard two-pass scheme: a dq pass (grid over q blocks,
stream k) and a dkv pass (grid over k blocks, stream q), both recomputing
p from the saved logsumexp — nothing quadratic is ever stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_compiler_params

NEG_INF = -2.3819763e38


def _apply_softcap(z, softcap):
    if softcap > 0:
        return jnp.tanh(z / softcap) * softcap
    return z


def _block_mask(iq, ik, bq, bk, *, causal, window):
    """(bq, bk) bool tile of allowed positions for blocks (iq, ik)."""
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allowed = jnp.ones((bq, bk), bool)
    if causal:
        allowed &= kpos <= qpos
    if window > 0:
        allowed &= kpos > qpos - window
    return allowed


def _block_live(iq, ik, bq, bk, *, causal, window):
    """Whether block (iq, ik) has ANY unmasked entry (python-traced scalar)."""
    live = jnp.array(True)
    if causal:
        live &= (ik * bk) <= (iq * bq + bq - 1)
    if window > 0:
        live &= (ik * bk + bk - 1) > (iq * bq - window)
    return live


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, window, softcap, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_block_live(iq, ik, bq, bk, causal=causal, window=window))
    def _step():
        q = q_ref[0, 0]                                # (bq, D)
        k = k_ref[0, 0]                                # (bk, D)
        z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        z = _apply_softcap(z, softcap)
        mask = _block_mask(iq, ik, bq, bk, causal=causal, window=window)
        z = jnp.where(mask, z, NEG_INF)

        m_prev = m_ref[:, 0]                           # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(z, axis=1))
        alpha = jnp.exp(m_prev - m_new)                # (bq,)
        p = jnp.exp(z - m_new[:, None])                # (bq, bk)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_new
        v = v_ref[0, 0]                                # (bk, Dv)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ik == nk - 1)
    def _flush():
        l = l_ref[...]                                 # (bq, 1)
        l_safe = jnp.where(l == 0, 1.0, l)             # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l == 0, NEG_INF, m_ref[...] + jnp.log(l_safe))


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "bq", "bk", "interpret"))
def flash_fwd(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
              bq=512, bk=512, interpret=False):
    """Returns (out, lse). Shapes: q (B,Hq,S,D), k (B,Hkv,T,D), v
    (B,Hkv,T,Dv), out (B,Hq,S,Dv), lse (B,Hq,S,1) — the trailing unit dim
    keeps the row statistics' block ``(bq, 1)``, which Mosaic's (8, 128)
    tiling rule accepts."""
    b, hq, s, d = q.shape
    hkv, t, dh_v = k.shape[1], k.shape[2], v.shape[3]
    qpk = hq // hkv
    scale = float(d ** -0.5) if scale is None else float(scale)
    bq = min(bq, s)
    bk = min(bk, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    nq, nk = s // bq, t // bk

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, softcap=softcap, nk=nk)
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(b, hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, h, i, j, qpk=qpk: (b, h // qpk, j, 0)),
                pl.BlockSpec((1, 1, bk, dh_v),
                             lambda b, h, i, j, qpk=qpk: (b, h // qpk, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, dh_v), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, hq, s, dh_v), q.dtype),
                jax.ShapeDtypeStruct((b, hq, s, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, dh_v), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            name="flash_fwd",
            interpret=interpret,
        )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: dq pass (grid over q blocks, stream k) and dkv pass (grid over
# k blocks, stream q). p is recomputed from the saved lse.
# ---------------------------------------------------------------------------

def _recompute_p_dz(q, k, lse_blk, do, v, delta_blk, *, scale, softcap,
                    mask):
    """Shared bwd math for one (bq, bk) tile; ``lse_blk``/``delta_blk``
    are (bq, 1) columns. Returns (p, dz), both f32."""
    z_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    z = _apply_softcap(z_raw, softcap)
    z = jnp.where(mask, z, NEG_INF)
    p = jnp.exp(z - lse_blk)                            # (bq, bk)
    p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dz = p * (dp - delta_blk)                           # d logits (post-cap)
    if softcap > 0:
        dz = dz * (1.0 - jnp.square(jnp.tanh(z_raw / softcap)))
    return p, dz


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, window, softcap, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(iq, ik, bq, bk, causal=causal, window=window))
    def _step():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        mask = _block_mask(iq, ik, bq, bk, causal=causal, window=window)
        _, dz = _recompute_p_dz(q, k, lse_ref[0, 0], do, v, delta_ref[0, 0],
                                scale=scale, softcap=softcap, mask=mask)
        acc_ref[...] += jax.lax.dot_general(
            dz.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _flush():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                softcap, nq, qpk):
    # grid: (B, Hkv, nk, qpk, nq) — for one kv block the (head-in-group,
    # q-block) accumulation dims are innermost, so the scratch accumulators
    # live exactly as long as one output block (consecutive revisits).
    ik, hg, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when((iq == 0) & (hg == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(iq, ik, bq, bk, causal=causal, window=window))
    def _step():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        mask = _block_mask(iq, ik, bq, bk, causal=causal, window=window)
        p, dz = _recompute_p_dz(q, k, lse_ref[0, 0], do, v,
                                delta_ref[0, 0], scale=scale,
                                softcap=softcap, mask=mask)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            dz.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when((iq == nq - 1) & (hg == qpk - 1))
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "bq", "bk", "interpret"))
def flash_bwd(q, k, v, out, lse, do, *, causal=True, window=0, softcap=0.0,
              scale=None, bq=512, bk=512, interpret=False):
    """Returns (dq, dk, dv)."""
    b, hq, s, d = q.shape
    hkv, t, dh_v = k.shape[1], k.shape[2], v.shape[3]
    qpk = hq // hkv
    scale = float(d ** -0.5) if scale is None else float(scale)
    bq = min(bq, s)
    bk = min(bk, t)
    # Same contract as flash_fwd. Without it, a caller passing a
    # non-dividing block silently drops the sequence tail: the grid is
    # floor(s/bq) × floor(t/bk), so dq/dk/dv tail tiles stay zero —
    # the coverage-gap class the static auditor
    # (repro.analysis.kernel_audit) checks for.
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    nq, nk = s // bq, t // bk

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)             # (B, Hq, S, 1)

    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              window=window, softcap=softcap, nk=nk),
            grid=(b, hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, h, i, j, qpk=qpk: (b, h // qpk, j, 0)),
                pl.BlockSpec((1, 1, bk, dh_v),
                             lambda b, h, i, j, qpk=qpk: (b, h // qpk, j, 0)),
                pl.BlockSpec((1, 1, bq, dh_v), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b, h, i, j: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            name="flash_dq",
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    with jax.named_scope("flash_dkdv"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              window=window, softcap=softcap, nq=nq, qpk=qpk),
            grid=(b, hkv, nk, qpk, nq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b, g, j, hg, i, qpk=qpk:
                             (b, g * qpk + hg, i, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, g, j, hg, i: (b, g, j, 0)),
                pl.BlockSpec((1, 1, bk, dh_v),
                             lambda b, g, j, hg, i: (b, g, j, 0)),
                pl.BlockSpec((1, 1, bq, dh_v),
                             lambda b, g, j, hg, i, qpk=qpk:
                             (b, g * qpk + hg, i, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, g, j, hg, i, qpk=qpk:
                             (b, g * qpk + hg, i, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, g, j, hg, i, qpk=qpk:
                             (b, g * qpk + hg, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, g, j, hg, i: (b, g, j, 0)),
                pl.BlockSpec((1, 1, bk, dh_v),
                             lambda b, g, j, hg, i: (b, g, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, hkv, t, d), k.dtype),
                jax.ShapeDtypeStruct((b, hkv, t, dh_v), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, dh_v), jnp.float32),
            ],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary", "arbitrary")),
            name="flash_dkdv",
            interpret=interpret,
        )(q, k, v, do, lse, delta)
    return dq, dk, dv
