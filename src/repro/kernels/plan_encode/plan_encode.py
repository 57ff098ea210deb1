"""Pallas TPU kernel: capacity-balanced group assignment (plan encode).

The OSEL analogue's last host-shaped remnant was ``balanced_assign``'s
global ``jnp.lexsort`` — a serial sort idiom XLA lowers outside any kernel.
This kernel replaces the sort with the comparator-array formulation the
FPGA's load-allocation unit suggests: counting sort by pairwise compares
plus prefix sums, all on VMEM tiles.

For every item ``i`` (a row of IG or a column of OG) the inputs are its
argmax group ``pref[i]`` and preference strength ``strength[i]``. The
placement is **fully tiled** — two passes over ``(bi, bj)`` item-tile
pairs, so the VMEM working set is ``(bi, bj)`` regardless of M and the
old 4096-item cap is gone:

  1. **rank** — grid ``(L, Mp/bi, Mp/bj)``: ``rank[i]`` counts the items
     of the same group that sort strictly before ``i`` (stronger, or equal
     strength with a smaller global index — the lexsort's stable
     tie-break), accumulated tile pair by tile pair in a ``(bi, 1)``
     scratch. At the last ``j`` tile the kernel also emits the i-tile's
     per-group histogram (one ``(1, G)`` row per tile) — the cross-tile
     carry the placement pass needs.
  2. **place** — grid ``(L, Mp/bi)``: per-group totals from the summed
     tile histograms, exclusive prefix sums over the G groups (a (G, G)
     strict-upper mask — the prefix-sum half of the formulation), and the
     closed-form slot of every item: kept items go to ``pref·cap + rank``;
     overflow items (``rank >= cap``) take the free slots in ascending
     slot order, located by matching their global overflow rank against
     the per-group free-slot ranges. Because rank and the histograms are
     global quantities, every i-tile places independently — spills that
     cross tile boundaries land bitwise where the lexsort puts them.

Output is ``slot_of_item`` (L, Mp, 1) int32; the inverse permutation
scatter back to ``(G, cap)`` buckets is memory-bound VPU work left to XLA
(the same split as ``flgw_matmul``'s gathers). Bitwise-identical to the
lexsort reference for finite scores; signed-zero strength ties may legally
differ (the reference sorts on ``-strength`` where ``-0.0 == 0.0``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_compiler_params


def _rank_kernel(pref_c_ref, str_c_ref, pref_r_ref, str_r_ref, rank_ref,
                 hist_ref, acc_ref, *, g: int, bi: int, bj: int, n_jt: int):
    """One (l, i-tile, j-tile) grid step of the comparator-rank pass."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pref_c = pref_c_ref[0]                                # (bi, 1) int32
    str_c = str_c_ref[0]                                  # (bi, 1) f32
    pref_j = pref_r_ref[0]                                # (1, bj)
    str_j = str_r_ref[0]                                  # (1, bj)
    ii = i * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
    jj = j * bj + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
    same = pref_c == pref_j                               # (bi, bj)
    before = (str_j > str_c) | ((str_j == str_c) & (jj < ii))
    acc_ref[...] += jnp.sum((same & before).astype(jnp.int32),
                            axis=1, keepdims=True)

    @pl.when(j == n_jt - 1)
    def _emit():
        rank_ref[0] = acc_ref[...]
        # This i-tile's group histogram — padding items carry the sentinel
        # group ``g`` and drop out of the (bi, G) one-hot.
        gi_row = jax.lax.broadcasted_iota(jnp.int32, (bi, g), 1)
        onehot = (pref_c == gi_row).astype(jnp.int32)
        hist_ref[0, 0] = jnp.sum(onehot, axis=0, keepdims=True)  # (1, G)


def _place_kernel(pref_c_ref, rank_ref, hist_ref, slot_ref, *, g: int,
                  cap: int, bi: int):
    """One (l, i-tile) grid step of the cross-tile placement pass."""
    # Per-group totals: sum of every i-tile's histogram (the cross-tile
    # reduction). Row layout for per-item gathers via the one-hot; the
    # column layout for the (G, G) prefix sums comes from an eye-mask
    # select (no (1, G) -> (G, 1) transposes in-kernel).
    counts_row = jnp.sum(hist_ref[0], axis=0, keepdims=True)       # (1, G)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (g, g), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (g, g), 1))
    counts_col = jnp.sum(
        jnp.where(eye, jnp.broadcast_to(counts_row, (g, g)), 0),
        axis=1, keepdims=True)                                     # (G, 1)
    kcount_row = jnp.minimum(counts_row, cap)
    kcount_col = jnp.minimum(counts_col, cap)
    # Exclusive prefix sums over groups: strict-upper (G, G) mask.
    tri = (jax.lax.broadcasted_iota(jnp.int32, (g, g), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (g, g), 1))
    ovf_before = jnp.sum(jnp.where(tri, counts_col - kcount_col, 0),
                         axis=0, keepdims=True)                    # (1, G)
    free_before = jnp.sum(jnp.where(tri, cap - kcount_col, 0),
                          axis=0, keepdims=True)                   # (1, G)

    pref_c = pref_c_ref[0]                                # (bi, 1) int32
    rank = rank_ref[0]                                    # (bi, 1) int32
    gi_row = jax.lax.broadcasted_iota(jnp.int32, (bi, g), 1)
    onehot = (pref_c == gi_row).astype(jnp.int32)         # (bi, G)

    def sel(row_vec):                                     # gather by pref
        return jnp.sum(onehot * row_vec, axis=1, keepdims=True)

    keep = rank < cap
    kept_slot = pref_c * cap + jnp.minimum(rank, cap - 1)
    # Overflow: global overflow rank, then match against the ascending
    # free-slot ranges [free_before[g], free_before[g] + nfree[g]).
    q = sel(ovf_before) + rank - cap                      # (bi, 1)
    nfree_row = cap - kcount_row                          # (1, G)
    match = ((q >= free_before) & (q < free_before + nfree_row)
             ).astype(jnp.int32)                          # (bi, G)
    gsel = jnp.sum(match * gi_row, axis=1, keepdims=True)
    kc_sel = jnp.sum(match * kcount_row, axis=1, keepdims=True)
    lo_sel = jnp.sum(match * free_before, axis=1, keepdims=True)
    ovf_slot = gsel * cap + kc_sel + (q - lo_sel)
    slot_ref[0] = jnp.where(keep, kept_slot, ovf_slot).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("g", "cap", "bi", "bj", "interpret"))
def assign_slots(pref_c: jax.Array, str_c: jax.Array, pref_r: jax.Array,
                 str_r: jax.Array, *, g: int, cap: int, bi: int, bj: int,
                 interpret: bool = False) -> jax.Array:
    """(L, Mp, 1)+(L, 1, Mp) pref/strength -> (L, Mp, 1) int32 slot ids.

    ``Mp`` must be a multiple of both ``bi`` and ``bj`` (ops.py pads;
    padding items carry ``pref == g`` / ``strength == -inf`` and produce
    garbage slots the caller drops). VMEM per rank step: the (bi, bj)
    comparator tile plus the (bi, G) one-hot — independent of M, so any
    item count tiles through; the cross-tile state is one (n_it, G)
    histogram per layer.
    """
    l, mp, _ = pref_c.shape
    assert mp % bi == 0 and mp % bj == 0, (mp, bi, bj)
    n_it = mp // bi
    n_jt = mp // bj

    with jax.named_scope("plan_encode_rank"):
        rank, hist = pl.pallas_call(
            functools.partial(_rank_kernel, g=g, bi=bi, bj=bj, n_jt=n_jt),
            grid=(l, n_it, n_jt),
            in_specs=[
                pl.BlockSpec((1, bi, 1), lambda i, ti, tj: (i, ti, 0)),
                pl.BlockSpec((1, bi, 1), lambda i, ti, tj: (i, ti, 0)),
                pl.BlockSpec((1, 1, bj), lambda i, ti, tj: (i, 0, tj)),
                pl.BlockSpec((1, 1, bj), lambda i, ti, tj: (i, 0, tj)),
            ],
            out_specs=[
                pl.BlockSpec((1, bi, 1), lambda i, ti, tj: (i, ti, 0)),
                # (1, G) rows as the two full trailing dims: Mosaic tiles
                # the last two block dims by (8, 128) unless they span the
                # whole array dimension.
                pl.BlockSpec((1, 1, 1, g), lambda i, ti, tj: (i, ti, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((l, mp, 1), jnp.int32),
                jax.ShapeDtypeStruct((l, n_it, 1, g), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((bi, 1), jnp.int32)],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            name="plan_encode_rank",
            interpret=interpret,
        )(pref_c, str_c, pref_r, str_r)

    hist = hist.reshape(l, n_it, g)
    with jax.named_scope("plan_encode_place"):
        return pl.pallas_call(
            functools.partial(_place_kernel, g=g, cap=cap, bi=bi),
            grid=(l, n_it),
            in_specs=[
                pl.BlockSpec((1, bi, 1), lambda i, ti: (i, ti, 0)),
                pl.BlockSpec((1, bi, 1), lambda i, ti: (i, ti, 0)),
                pl.BlockSpec((1, n_it, g), lambda i, ti: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bi, 1), lambda i, ti: (i, ti, 0)),
            out_shape=jax.ShapeDtypeStruct((l, mp, 1), jnp.int32),
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            name="plan_encode_place",
            interpret=interpret,
        )(pref_c, rank, hist)
