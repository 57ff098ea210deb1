"""Pallas TPU kernel: grouped (block-diagonal) batched matmul for FLGW.

This is the compute hot-spot of the LearningGroup accelerator, re-architected
for the TPU MXU. OSEL observation 2 says the FLGW mask consists of at most G
distinct row patterns, i.e. after a balanced group permutation the masked
matmul *is* G independent dense tiles:

    y_c[g] = x_c[g] @ W_c[g]          (G, B, capM) x (G, capM, capN)

The FPGA realizes this with 264-wide FP16 VPU rows and 2-bit activation mux
selects; the TPU-native equivalent is a dense batched matmul whose tiles are
MXU-aligned (multiples of 128 in the contracted/output dims) and staged
HBM→VMEM via BlockSpec. Compute drops by exactly G versus the dense layer.

Grid: (G, B/bb, capN/bn, capM/bk) with accumulation over the bk axis in an
f32 VMEM scratch accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_compiler_params


def _bmm_kernel(xg_ref, wc_ref, out_ref, acc_ref, *, k_steps: int):
    """One (g, b-tile, n-tile, k-tile) grid step."""

    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU matmul on the current VMEM tiles; accumulate in f32.
    acc_ref[...] += jax.lax.dot_general(
        xg_ref[0], wc_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _flush():
        out_ref[0, ...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "bn", "bk", "interpret"))
def grouped_bmm(xg: jax.Array, wc: jax.Array, *, bb: int = 128,
                bn: int = 128, bk: int = 128,
                interpret: bool = False) -> jax.Array:
    """(G, B, capM) @ (G, capM, capN) -> (G, B, capN).

    Dims must be multiples of the tile sizes (ops.py pads). Tile sizes default
    to 128 to align the MXU systolic array; the f32 accumulator tile is
    (bb, bn) in VMEM scratch. The per-step VMEM working set is audited
    statically over a shape corpus — see ``audit.py`` beside this module
    and ``python -m repro.analysis.kernel_audit`` for the numbers.
    """
    g, b, m = xg.shape
    g2, m2, n = wc.shape
    assert g == g2 and m == m2, (xg.shape, wc.shape)
    assert b % bb == 0 and n % bn == 0 and m % bk == 0, (xg.shape, wc.shape)
    k_steps = m // bk

    with jax.named_scope("grouped_bmm"):
        return pl.pallas_call(
            functools.partial(_bmm_kernel, k_steps=k_steps),
            grid=(g, b // bb, n // bn, k_steps),
            in_specs=[
                pl.BlockSpec((1, bb, bk), lambda g, i, j, k: (g, i, k)),
                pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bb, bn), lambda g, i, j, k: (g, i, j)),
            out_shape=jax.ShapeDtypeStruct((g, b, n), xg.dtype),
            scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
            ),
            name="grouped_bmm",
            interpret=interpret,
        )(xg, wc)
