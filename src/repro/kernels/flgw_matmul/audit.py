"""KernelSpec for the FLGW grouped-matmul kernel (jax-free).

Mirrors the exact grid/BlockSpec construction of
``flgw_matmul.grouped_bmm`` as driven by the ``ops.py`` wrapper (same
:mod:`repro.kernels.tiling` helpers, same padding), so
:mod:`repro.analysis.kernel_audit` can prove bounds / coverage /
write-disjointness / VMEM for a whole shape corpus without compiling
anything. The contracted ``k`` axis (grid axis 3) is the
declared accumulation axis: every output tile is legitimately revisited
once per k-step into the f32 VMEM scratch accumulator.

Corpus cases are given in the *caller's* terms — dense (M, N), group
count G, capacity slack — and compacted through the same
``compute_cap`` rule the plan encoder uses, so the ``slack > 1``
capacity-stretch geometry is part of what gets proven.
"""
from __future__ import annotations

from repro.analysis.kernel_audit import (GridCase, KernelSpec, Operand,
                                         register_kernel_spec)
from repro.kernels.tiling import compute_cap, pick_tile, round_up

F32 = 4


def _tiles(b: int, cap_m: int, cap_n: int):
    bb = pick_tile(b, 128)
    bn = pick_tile(cap_n, 128)
    bk = pick_tile(cap_m, 128)
    return (bb, bn, bk, round_up(b, bb), round_up(cap_m, bk),
            round_up(cap_n, bn))


def _caps(p: dict):
    g = p["g"]
    cap_m = compute_cap(p["m"], g, p.get("slack", 1.0))
    cap_n = compute_cap(p["n"], g, p.get("slack", 1.0))
    return g, cap_m, cap_n


def _label(p: dict) -> str:
    s = p.get("slack", 1.0)
    return (f"b{p['b']}_m{p['m']}_n{p['n']}_g{p['g']}"
            + (f"_slack{s}" if s != 1.0 else ""))


def _tags(p: dict):
    tags = []
    if max(p["m"], p["n"]) > 4096:
        tags.append("m_gt_4096")
    if p.get("slack", 1.0) > 1.0:
        tags.append("slack_gt_1")
    return tuple(tags)


def _grouped_bmm_case(p: dict) -> GridCase:
    g, cap_m, cap_n = _caps(p)
    dt = p.get("itemsize", F32)
    bb, bn, bk, bp, mp, np_ = _tiles(p["b"], cap_m, cap_n)
    grid = (g, bp // bb, np_ // bn, mp // bk)
    return GridCase(
        label=_label(p), grid=grid,
        operands=(
            Operand("xg", (g, bp, mp), (1, bb, bk),
                    lambda gi, i, j, k: (gi, i, k), dt),
            Operand("wc", (g, mp, np_), (1, bk, bn),
                    lambda gi, i, j, k: (gi, k, j), dt),
            Operand("yc", (g, bp, np_), (1, bb, bn),
                    lambda gi, i, j, k: (gi, i, j), dt, role="out"),
        ),
        accum_axes=frozenset({3}),
        scratch_bytes=bb * bn * F32,
        tags=_tags(p),
    )


register_kernel_spec(KernelSpec(
    name="flgw_matmul.grouped_bmm",
    module="repro.kernels.flgw_matmul.flgw_matmul",
    build=_grouped_bmm_case,
    corpus=(
        {"b": 2, "m": 64, "n": 64, "g": 4},           # decode-tiny
        {"b": 128, "m": 1024, "n": 1024, "g": 8},     # training tile
        {"b": 64, "m": 512, "n": 512, "g": 4, "slack": 1.5},
        {"b": 32, "m": 8192, "n": 8192, "g": 16},     # d_ff scale
        {"b": 2, "m": 8192, "n": 8192, "g": 4},       # d_ff decode
        {"b": 8, "m": 4352, "n": 512, "g": 8, "slack": 1.25},
    ),
    note="XLA-gather grouped path; k accumulates in VMEM scratch",
))
