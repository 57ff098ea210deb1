"""Fig. 10 — OSEL sparse-data-generation efficiency (cycles + memory).

Reproduces the paper's claims analytically from the cycle/footprint models
of the FPGA encoding loop (repro.core.osel): OSEL vs the recompute-every-row
baseline on a 128×512 mask, G ∈ {2, 4, 8, 16, 32}.

Paper targets: up to 5.72× cycle reduction, 1.95–6.81× memory compression.
Also times the *vectorized TPU-path* encoder (jit on this host) to show the
index-compare encode is microseconds — the overhead the paper hides
on-chip stays hidden on TPU — and *measures* the full plan encode
(``make_plan``) both ways: the old lexsort/searchsorted idiom (generic XLA
ops outside any kernel) vs the ``plan_encode`` Pallas kernel, interleaved
(`timeit_interleaved`) so host timing drift hits both variants equally.

The M-sweep (committed artifact ``BENCH_fig10_osel.json``) crosses the old
4096-item tile cap that used to force a lexsort fallback. Above it, the
quantity that matters is the *amortized refresh window* — the paper's
encode-once/consume-many dataflow: one plan encode (+ one weight
compaction, post-PR) followed by ``WINDOW`` grouped consume steps.

* pre-PR:  lexsort encode, then per-step XLA gathers of both operands
  (``grouped_matmul``) — W re-gathered every step;
* fused:   tiled-kernel encode + ``compact_weights`` once, then per-step
  ``grouped_matmul`` fed the cached ``(G, cap)`` compact weights straight
  from the encode output (the OSEL→core handoff; no per-step W gather).

``kernel_beats_lexsort_above_4096`` asserts the fused window wins at every
M > 4096 cell. On a CPU host both kernels run in interpret mode (the
isolated encode *loses* there — the committed per-piece timings show it);
the window still flips because the per-step W-gather the fused path
retires outweighs the interpreted encode deficit.

``--check`` is the CI gate: bitwise oversize encode + fused-vs-gather
grouped step in interpret mode, plus schema/flag validation of the
committed artifact. No timing — CI boxes are too noisy to gate on a
single-digit-percent wall-clock margin.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import (REPO_ROOT, row, save, timeit,
                               timeit_interleaved, write_bench_json)
from repro import kernels as kernels_mod
from repro.core.grouped import make_plan
from repro.core.osel import cycle_model, encode, footprint_model
from repro.kernels.flgw_matmul import ops as fops

M, N = 128, 512

# M-sweep across the old 4096-item cap; N scales with M so the consume
# step stays W-gather-bound (the contrast the fused path retires).
SWEEP = (2048, 4096, 8192)
SWEEP_G, SWEEP_B, SWEEP_SLACK = 8, 4, 1.25
WINDOW = 8          # consume steps per encode (decode steps per refresh)


def _plan_timers(ig, og, slack=1.0):
    """Two compiled make_plan variants: lexsort reference vs Pallas encode.

    The impl is baked at trace time (the shared reference-impl switch), so
    each closure is traced under its mode once and then timed round-robin.
    """
    lex = jax.jit(lambda a, b: make_plan(a, b, slack))
    with kernels_mod.use_reference_impl():
        jax.block_until_ready(lex(ig, og))       # trace with the lexsort
    ker = jax.jit(lambda a, b: make_plan(a, b, slack))
    jax.block_until_ready(ker(ig, og))           # trace with the kernel
    return {"lexsort": lex, "pallas": ker}


def _sweep_inputs(m, n, g=SWEEP_G, b=SWEEP_B):
    key = jax.random.PRNGKey(m)
    x = jax.random.normal(key, (b, m))
    w = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    ig = jax.random.normal(jax.random.fold_in(key, 2), (m, g))
    og = jax.random.normal(jax.random.fold_in(key, 3), (g, n))
    return x, w, ig, og


def _sweep_cell(m, reps=5):
    """One amortized-window cell: encode + WINDOW consume steps, both ways."""
    n = m // 4
    x, w, ig, og = _sweep_inputs(m, n)
    enc = timeit_interleaved(_plan_timers(ig, og, SWEEP_SLACK), ig, og,
                             reps=reps, stat="median")
    plan = make_plan(ig, og, SWEEP_SLACK)
    t_compact = timeit(jax.jit(fops.compact_weights), w, plan.row_ids,
                       plan.col_ids, plan.row_valid, plan.col_valid)
    wc = fops.compact_weights(w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid)
    gather = jax.jit(lambda x, w: fops.grouped_matmul(
        x, w, plan.row_ids, plan.col_ids, plan.row_valid, plan.col_valid,
        interpret=True))
    fused = jax.jit(lambda x, wc: fops.grouped_matmul(
        x, w, plan.row_ids, plan.col_ids, plan.row_valid, plan.col_valid,
        wc, interpret=True))
    consume = timeit_interleaved(
        {"gather": lambda: gather(x, w), "fused": lambda: fused(x, wc)},
        reps=reps, stat="median")
    pre = enc["lexsort"] + WINDOW * consume["gather"]
    post = enc["pallas"] + t_compact + WINDOW * consume["fused"]
    return {"M": m, "N": n, "above_cap": m > 4096,
            "enc_lexsort_s": enc["lexsort"], "enc_kernel_s": enc["pallas"],
            "compact_s": t_compact,
            "consume_gather_s": consume["gather"],
            "consume_fused_s": consume["fused"],
            "window_pre_pr_s": pre, "window_fused_s": post,
            "window_speedup": pre / post}


def _oversize_bitwise(m=4352, g=SWEEP_G, b=SWEEP_B, n=512):
    """M > old cap: kernel encode bitwise vs lexsort, and the fused
    grouped step bitwise vs the XLA-gather step — both interpret mode."""
    x, w, ig, og = _sweep_inputs(m, n, g, b)
    plan = make_plan(ig, og, SWEEP_SLACK)
    with kernels_mod.use_reference_impl():
        ref = make_plan(ig, og, SWEEP_SLACK)
    enc_ok = all(bool(jnp.array_equal(a, b)) for a, b in
                 zip(jax.tree.leaves(plan), jax.tree.leaves(ref)))
    wc = fops.compact_weights(w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid)
    y_fused = fops.grouped_matmul(
        x, w, plan.row_ids, plan.col_ids, plan.row_valid, plan.col_valid,
        wc, interpret=True)
    y_gather = fops.grouped_matmul(
        x, w, plan.row_ids, plan.col_ids, plan.row_valid, plan.col_valid,
        interpret=True)
    step_ok = bool(jnp.array_equal(y_fused, y_gather))
    return enc_ok, step_ok


def check() -> int:
    """CI gate: oversize encode + fused grouped step, bitwise, interpret;
    plus the committed artifact's schema and acceptance flags."""
    enc_ok, step_ok = _oversize_bitwise()
    row("# check: oversize encode bitwise", enc_ok)
    row("# check: fused grouped step bitwise", step_ok)
    ok = enc_ok and step_ok
    path = REPO_ROOT / "BENCH_fig10_osel.json"
    if not path.exists():
        row("# check: MISSING", str(path))
        return 1
    doc = json.loads(path.read_text())
    flags = doc.get("acceptance", {})
    for name, val in flags.items():
        row(f"# check: committed acceptance[{name}]", val)
        ok = ok and val is True
    ok = ok and {"config", "results"} <= doc.keys()
    return 0 if ok else 1


def main() -> dict:
    out = {"cells": []}
    row("# fig10_osel: mask", f"{M}x{N}")
    row("G", "base_cycles", "osel_cycles", "cycle_speedup",
        "dense_bytes", "osel_bytes", "mem_compression", "encode_us",
        "plan_lexsort_us", "plan_pallas_us")
    best_cyc, best_mem = 0.0, 0.0
    for g in (2, 4, 8, 16, 32):
        base = cycle_model(M, N, g, use_osel=False)
        osel = cycle_model(M, N, g, use_osel=True)
        dense = footprint_model(M, N, g, use_grouping=False)
        sparse = footprint_model(M, N, g, use_grouping=True)
        cyc = base["total"] / osel["total"]
        mem = dense["total"] / sparse["total"]
        best_cyc, best_mem = max(best_cyc, cyc), max(best_mem, mem)

        key = jax.random.PRNGKey(g)
        ig_idx = jax.random.randint(key, (M,), 0, g, jnp.int32)
        og_idx = jax.random.randint(jax.random.fold_in(key, 1), (N,), 0, g,
                                    jnp.int32)
        enc = jax.jit(lambda a, b, g=g: encode(a, b, g))
        us = timeit(enc, ig_idx, og_idx) * 1e6

        # measured device encode: full make_plan, lexsort vs Pallas
        ig = jax.random.normal(jax.random.fold_in(key, 2), (M, g))
        og = jax.random.normal(jax.random.fold_in(key, 3), (g, N))
        best = timeit_interleaved(_plan_timers(ig, og), ig, og,
                                  stat="median")
        lex_us, ker_us = best["lexsort"] * 1e6, best["pallas"] * 1e6

        row(g, base["total"], osel["total"], f"{cyc:.2f}",
            dense["total"], int(sparse["total"]), f"{mem:.2f}",
            f"{us:.1f}", f"{lex_us:.1f}", f"{ker_us:.1f}")
        out["cells"].append({
            "G": g, "base_cycles": base["total"],
            "osel_cycles": osel["total"], "cycle_speedup": cyc,
            "osel_breakdown": osel, "mem_dense": dense["total"],
            "mem_osel": sparse["total"], "mem_compression": mem,
            "mem_breakdown": sparse, "tpu_encode_us": us,
            "plan_lexsort_us": lex_us, "plan_pallas_us": ker_us,
            "plan_encode_interpret": jax.default_backend() != "tpu"})
    out["max_cycle_speedup"] = best_cyc
    out["max_mem_compression"] = best_mem
    row("# paper: cycles up to 5.72x, memory 1.95-6.81x; measured:",
        f"{best_cyc:.2f}x", f"{best_mem:.2f}x")

    # -- M-sweep across the old 4096 tile cap (amortized refresh window) --
    row(f"# M-sweep: g={SWEEP_G} b={SWEEP_B} slack={SWEEP_SLACK}"
        f" window={WINDOW} (encode + K consume steps, medians)")
    row("M", "N", "enc_lex_ms", "enc_ker_ms", "compact_ms",
        "consume_gather_ms", "consume_fused_ms", "window_speedup")
    sweep = []
    for m in SWEEP:
        c = _sweep_cell(m)
        sweep.append(c)
        row(c["M"], c["N"], f"{c['enc_lexsort_s'] * 1e3:.1f}",
            f"{c['enc_kernel_s'] * 1e3:.1f}", f"{c['compact_s'] * 1e3:.1f}",
            f"{c['consume_gather_s'] * 1e3:.1f}",
            f"{c['consume_fused_s'] * 1e3:.1f}",
            f"{c['window_speedup']:.3f}")
    out["sweep"] = sweep
    enc_ok, step_ok = _oversize_bitwise()
    above = [c for c in sweep if c["above_cap"]]
    beats = bool(above) and all(c["window_speedup"] > 1.0 for c in above)
    row("# kernel_beats_lexsort_above_4096:", beats,
        "(amortized window; per-piece medians committed)")
    save("fig10_osel", out)
    write_bench_json("fig10_osel", {
        "config": {"mask_m": M, "mask_n": N, "sweep_g": SWEEP_G,
                   "sweep_b": SWEEP_B, "sweep_slack": SWEEP_SLACK,
                   "window": WINDOW, "backend": jax.default_backend(),
                   "interpret": jax.default_backend() != "tpu"},
        "results": {"max_cycle_speedup": best_cyc,
                    "max_mem_compression": best_mem, "sweep": sweep},
        "acceptance": {
            "kernel_beats_lexsort_above_4096": beats,
            "oversize_encode_bitwise": bool(enc_ok),
            "fused_step_bitwise": bool(step_ok),
        }})
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="CI gate: bitwise oversize encode + fused step, "
                         "plus committed-artifact validation (no timing)")
    if ap.parse_args().check:
        sys.exit(check())
    main()
