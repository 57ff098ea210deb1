"""KernelSpecs for the plan-encode (balanced-assign) kernels (jax-free).

Two ``pallas_call`` sites in :mod:`plan_encode.assign_slots`:

* **rank** — grid ``(L, Mp/b, Mp/b)``: the j-tile axis (grid axis 2) is
  the declared accumulation axis; rank and the per-i-tile histogram are
  flushed at the last j tile, so both outputs are revisited ``n_jt``
  times consecutively.
* **place** — grid ``(L, Mp/b)``: every slot tile written exactly once,
  while the full per-layer ``(n_it, G)`` histogram rides along as an
  in-block broadcast — the one operand here whose VMEM cost grows with
  M (by ``M / b`` rows), which is exactly what the vmem check watches.

Tiling mirrors ``ops.py`` via :func:`repro.kernels.tiling.plan_block`:
the lifted 4096-item cap means the corpus must prove the multi-tile
geometry, so cases force ``block`` below M and push M well past 4096.
"""
from __future__ import annotations

from repro.analysis.kernel_audit import (GridCase, KernelSpec, Operand,
                                         register_kernel_spec)
from repro.kernels.tiling import plan_block, round_up

I32 = 4
F32 = 4


def _geom(p: dict):
    m = p["m"]
    b = plan_block(m, p.get("block"))
    mp = round_up(m, b)
    return p["l"], m, p["g"], b, mp, mp // b


def _label(p: dict) -> str:
    blk = p.get("block")
    return (f"l{p['l']}_m{p['m']}_g{p['g']}"
            + (f"_b{blk}" if blk else ""))


def _tags(p: dict):
    return ("m_gt_4096",) if p["m"] > 4096 else ()


def _rank_case(p: dict) -> GridCase:
    l, m, g, b, mp, n_t = _geom(p)
    return GridCase(
        label=_label(p), grid=(l, n_t, n_t),
        operands=(
            Operand("pref_c", (l, mp, 1), (1, b, 1),
                    lambda i, ti, tj: (i, ti, 0), I32),
            Operand("str_c", (l, mp, 1), (1, b, 1),
                    lambda i, ti, tj: (i, ti, 0), F32),
            Operand("pref_r", (l, 1, mp), (1, 1, b),
                    lambda i, ti, tj: (i, 0, tj), I32),
            Operand("str_r", (l, 1, mp), (1, 1, b),
                    lambda i, ti, tj: (i, 0, tj), F32),
            Operand("rank", (l, mp, 1), (1, b, 1),
                    lambda i, ti, tj: (i, ti, 0), I32, role="out"),
            Operand("hist", (l, n_t, 1, g), (1, 1, 1, g),
                    lambda i, ti, tj: (i, ti, 0, 0), I32, role="out"),
        ),
        accum_axes=frozenset({2}),
        scratch_bytes=b * 1 * I32,
        tags=_tags(p),
    )


def _place_case(p: dict) -> GridCase:
    l, m, g, b, mp, n_t = _geom(p)
    return GridCase(
        label=_label(p), grid=(l, n_t),
        operands=(
            Operand("pref_c", (l, mp, 1), (1, b, 1),
                    lambda i, ti: (i, ti, 0), I32),
            Operand("rank", (l, mp, 1), (1, b, 1),
                    lambda i, ti: (i, ti, 0), I32),
            Operand("hist", (l, n_t, g), (1, n_t, g),
                    lambda i, ti: (i, 0, 0), I32),
            Operand("slot", (l, mp, 1), (1, b, 1),
                    lambda i, ti: (i, ti, 0), I32, role="out"),
        ),
        tags=_tags(p),
    )


_CORPUS = (
    {"l": 1, "m": 256, "g": 4, "block": 128},   # forced multi-tile
    {"l": 2, "m": 4352, "g": 8},                # past the lifted cap
    {"l": 1, "m": 8192, "g": 64},               # d_ff-scale histogram
)

register_kernel_spec(KernelSpec(
    name="plan_encode.rank",
    module="repro.kernels.plan_encode.plan_encode",
    build=_rank_case, corpus=_CORPUS,
    note="comparator-rank pass; j-tile axis accumulates",
))
register_kernel_spec(KernelSpec(
    name="plan_encode.place",
    module="repro.kernels.plan_encode.plan_encode",
    build=_place_case, corpus=_CORPUS,
    note="prefix-sum placement; every tile written once",
))
