"""Pallas TPU kernel: OSEL mask encoding by index comparison.

OSEL observation 1: ``Mask[i,j] = (ig_idx[i] == og_idx[j])``. The FPGA
implements this with a comparator array fed by the two index lists; the TPU
equivalent is a VPU outer-equality over VMEM tiles of the index vectors —
O(M·N) 8-bit compares instead of the baseline's O(M·G·N) matmul, and no
M×G / G×N one-hot materialization.

The index vectors are carried as (M, 1) and (1, N) int32 so tiles respect
TPU (sublane, lane) layout. Output is uint8 (bitvector tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_compiler_params
# Shared with repro.kernels.osel_encode.audit so the audited grid is, by
# construction, the grid this wrapper builds.
from repro.kernels.tiling import round_up


def _encode_kernel(ig_ref, og_ref, mask_ref):
    ig = ig_ref[...]          # (bm, 1)
    og = og_ref[...]          # (1, bn)
    mask_ref[...] = (ig == og).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def encode_mask(ig_idx: jax.Array, og_idx: jax.Array, *, bm: int = 256,
                bn: int = 256, interpret: bool = False) -> jax.Array:
    """(M,) int32, (N,) int32 -> (M, N) uint8 mask."""
    m, n = ig_idx.shape[0], og_idx.shape[0]
    bm = min(bm, m)
    bn = min(bn, n)
    mp = round_up(m, bm)
    np_ = round_up(n, bn)
    ig2 = jnp.pad(ig_idx.astype(jnp.int32), (0, mp - m),
                  constant_values=-1)[:, None]
    og2 = jnp.pad(og_idx.astype(jnp.int32), (0, np_ - n),
                  constant_values=-2)[None, :]
    with jax.named_scope("osel_encode"):
        out = pl.pallas_call(
            _encode_kernel,
            grid=(mp // bm, np_ // bn),
            in_specs=[
                pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.uint8),
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel"),
            ),
            name="osel_encode",
            interpret=interpret,
        )(ig2, og2)
    return out[:m, :n]
