"""KernelSpecs for the flash-attention kernels (jax-free).

Mirrors ``flash_attention.flash_fwd`` / ``flash_bwd``'s grids exactly as
the ``ops.py`` wrapper drives them (``pick_block`` divisor selection,
GQA ``h // qpk`` index maps), for the static auditor. Accumulation
declarations:

* fwd / bwd-dq: the k-block axis (grid axis 3) — online-softmax /
  dq accumulate in VMEM scratch and flush at the last k block;
* bwd-dkv: grid ``(B, Hkv, nk, qpk, nq)`` with the (head-in-group,
  q-block) axes 3 and 4 declared — one dk/dv tile is revisited
  ``qpk * nq`` times, and the revisits must be consecutive (both axes
  innermost), which is precisely what the disjointness check proves.
"""
from __future__ import annotations

from repro.analysis.kernel_audit import (GridCase, KernelSpec, Operand,
                                         register_kernel_spec)
from repro.kernels.tiling import pick_block

F32 = 4


def _blocks(p: dict):
    bq = min(pick_block(p["s"], p.get("bq", 512)), p["s"])
    bk = min(pick_block(p["t"], p.get("bk", 512)), p["t"])
    return bq, bk, p["s"] // bq, p["t"] // bk


def _label(p: dict) -> str:
    return (f"b{p['b']}_h{p['hq']}kv{p['hkv']}_s{p['s']}_t{p['t']}"
            f"_d{p['d']}")


def _tags(p: dict):
    return ("m_gt_4096",) if max(p["s"], p["t"]) > 4096 else ()


def _fwd_case(p: dict) -> GridCase:
    b, hq, hkv, d = p["b"], p["hq"], p["hkv"], p["d"]
    s, t = p["s"], p["t"]
    dt = p.get("itemsize", F32)
    qpk = hq // hkv
    bq, bk, nq, nk = _blocks(p)
    return GridCase(
        label=_label(p), grid=(b, hq, nq, nk),
        operands=(
            Operand("q", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, h, i, j: (bi, h, i, 0), dt),
            Operand("k", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, h, i, j, qpk=qpk: (bi, h // qpk, j, 0),
                    dt),
            Operand("v", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, h, i, j, qpk=qpk: (bi, h // qpk, j, 0),
                    dt),
            Operand("out", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, h, i, j: (bi, h, i, 0), dt, role="out"),
            Operand("lse", (b, hq, s, 1), (1, 1, bq, 1),
                    lambda bi, h, i, j: (bi, h, i, 0), F32, role="out"),
        ),
        accum_axes=frozenset({3}),
        scratch_bytes=(bq * d + bq + bq) * F32,
        tags=_tags(p),
    )


def _dq_case(p: dict) -> GridCase:
    b, hq, hkv, d = p["b"], p["hq"], p["hkv"], p["d"]
    s, t = p["s"], p["t"]
    dt = p.get("itemsize", F32)
    qpk = hq // hkv
    bq, bk, nq, nk = _blocks(p)
    return GridCase(
        label=_label(p), grid=(b, hq, nq, nk),
        operands=(
            Operand("q", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, h, i, j: (bi, h, i, 0), dt),
            Operand("k", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, h, i, j, qpk=qpk: (bi, h // qpk, j, 0),
                    dt),
            Operand("v", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, h, i, j, qpk=qpk: (bi, h // qpk, j, 0),
                    dt),
            Operand("do", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, h, i, j: (bi, h, i, 0), dt),
            Operand("lse", (b, hq, s, 1), (1, 1, bq, 1),
                    lambda bi, h, i, j: (bi, h, i, 0), F32),
            Operand("delta", (b, hq, s, 1), (1, 1, bq, 1),
                    lambda bi, h, i, j: (bi, h, i, 0), F32),
            Operand("dq", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, h, i, j: (bi, h, i, 0), dt, role="out"),
        ),
        accum_axes=frozenset({3}),
        scratch_bytes=bq * d * F32,
        tags=_tags(p),
    )


def _dkv_case(p: dict) -> GridCase:
    b, hq, hkv, d = p["b"], p["hq"], p["hkv"], p["d"]
    s, t = p["s"], p["t"]
    dt = p.get("itemsize", F32)
    qpk = hq // hkv
    bq, bk, nq, nk = _blocks(p)
    return GridCase(
        label=_label(p), grid=(b, hkv, nk, qpk, nq),
        operands=(
            Operand("q", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, g, j, hg, i, qpk=qpk:
                    (bi, g * qpk + hg, i, 0), dt),
            Operand("k", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, g, j, hg, i: (bi, g, j, 0), dt),
            Operand("v", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, g, j, hg, i: (bi, g, j, 0), dt),
            Operand("do", (b, hq, s, d), (1, 1, bq, d),
                    lambda bi, g, j, hg, i, qpk=qpk:
                    (bi, g * qpk + hg, i, 0), dt),
            Operand("lse", (b, hq, s, 1), (1, 1, bq, 1),
                    lambda bi, g, j, hg, i, qpk=qpk:
                    (bi, g * qpk + hg, i, 0), F32),
            Operand("delta", (b, hq, s, 1), (1, 1, bq, 1),
                    lambda bi, g, j, hg, i, qpk=qpk:
                    (bi, g * qpk + hg, i, 0), F32),
            Operand("dk", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, g, j, hg, i: (bi, g, j, 0), dt,
                    role="out"),
            Operand("dv", (b, hkv, t, d), (1, 1, bk, d),
                    lambda bi, g, j, hg, i: (bi, g, j, 0), dt,
                    role="out"),
        ),
        accum_axes=frozenset({3, 4}),
        scratch_bytes=2 * bk * d * F32,
        tags=_tags(p),
    )


_CORPUS = (
    {"b": 2, "hq": 8, "hkv": 2, "s": 1024, "t": 1024, "d": 64},  # GQA
    {"b": 1, "hq": 4, "hkv": 4, "s": 512, "t": 512, "d": 128,
     "itemsize": 2},                                      # MHA, bf16
    {"b": 1, "hq": 2, "hkv": 1, "s": 4352, "t": 4352, "d": 64},
    {"b": 2, "hq": 4, "hkv": 4, "s": 128, "t": 384, "d": 64},  # cross
)

register_kernel_spec(KernelSpec(
    name="flash_attention.flash_fwd",
    module="repro.kernels.flash_attention.flash_attention",
    build=_fwd_case, corpus=_CORPUS,
    note="online-softmax fwd; k-block axis accumulates",
))
register_kernel_spec(KernelSpec(
    name="flash_attention.flash_bwd_dq",
    module="repro.kernels.flash_attention.flash_attention",
    build=_dq_case, corpus=_CORPUS,
    note="bwd dq pass; k-block axis accumulates",
))
register_kernel_spec(KernelSpec(
    name="flash_attention.flash_bwd_dkv",
    module="repro.kernels.flash_attention.flash_attention",
    build=_dkv_case, corpus=_CORPUS,
    note="bwd dkv pass; (head-in-group, q-block) axes accumulate",
))
