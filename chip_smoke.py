#!/usr/bin/env python3
"""Bring-up smoke run of the main path on a TPU, through the user entry points.

  python chip_smoke.py              # one chip: phases A, B and C
  python chip_smoke.py --chips 4    # four chips: the (env, agent) mesh path only

A. MARL training, the paper's main path at the paper's width: IC3Net
   (hidden 128, 8 agents) with FLGW G=4 on the grouped path, Predator-Prey,
   env batch 32, through ``repro.marl.train.train`` — 3 scan windows of 5
   iterations with a plan refresh every 3, so one lands inside a window.
   The masked path (the paper-faithful oracle) runs from the same seed.
B. The main-path Pallas kernels at real widths against their ``ref.py``.
C. Serving gemma2-2b (bf16, FLGW G=4 on the MLPs) through ``ServeSession``
   and ``Engine``: 4 requests of 32 prompt tokens and 16 new tokens.
M. (``--chips 4`` only) phase A's config on a (2, 2) ``make_marl_mesh``,
   against the same global batch and seed on one chip.

Weights are random, made from ``--seed``. Every check raises, so a failed
phase exits non-zero and prints no result line. The last line of standard
output is one JSON object with the device JAX reports. Times and bytes
printed here are smoke output, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# Phase A/M sizes: the paper's largest env batch, log windows of 5.
MARL_BATCH, WINDOWS, WINDOW, REFRESH_EVERY = 32, 3, 5, 3
# Masked vs grouped trajectories drift: the capacity-balanced layout spills
# a few rows, dIG/dOG use the sparse-restricted STE, and a flipped sampled
# action changes the episode. Same bound as tests/test_marl.py.
LOSS_RTOL = LOSS_ATOL = 0.5


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX found "
                         f"{len(devices)} device(s)")
    return devices


def assert_kernel(hlo_text: str, what: str) -> None:
    """A compiled program holds a Mosaic kernel: the Pallas kernel ran,
    not interpret mode and not the jnp reference."""
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no tpu_custom_call in the program")


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def check_close(name: str, got, want, tol: float, why: str) -> None:
    err = rel_err(got, want)
    log(f"  {name}: max|err|/max|ref| = {err:.3e} (tol {tol:g}: {why})")
    if not err <= tol:                       # also catches NaN
        raise AssertionError(f"{name}: error {err} above {tol}")


# ---------------------------------------------------------------------------
# A. MARL training
# ---------------------------------------------------------------------------

def _compile_chunk(chunk, cfg, ecfg, tcfg, env, sched, seed, contexts=None):
    """Lower and compile the scan window ``train`` will run; the run then
    finds it in the persistent compilation cache."""
    import contextlib

    import jax.numpy as jnp
    from repro.marl import train as T
    cfg2, key, params, opt = T._init(cfg, ecfg, env, seed)
    plans = T._encode_plans(params, cfg2)
    t0 = time.perf_counter()
    with contexts or contextlib.nullcontext():
        lowered = chunk.lower(params, opt, key, plans,
                              jnp.asarray(0, jnp.int32), WINDOW, cfg2, ecfg,
                              tcfg, env, sched)
    compiled = lowered.compile()
    return lowered, compiled, time.perf_counter() - t0


def _train_losses(cfg, ecfg, tcfg, env, sched, seed):
    import numpy as np
    from repro.marl import train as T
    params, hist = T.train(cfg, ecfg, tcfg, iterations=WINDOWS * WINDOW,
                           seed=seed, log_every=WINDOW, env=env,
                           schedule=sched)
    losses = np.array([h["loss"] for h in hist])
    if len(losses) != WINDOWS * WINDOW or not np.isfinite(losses).all():
        raise AssertionError(f"non-finite or missing losses: {losses}")
    return params, losses


def phase_marl(cfg, ecfg, seed: int) -> None:
    import numpy as np
    from repro.core.schedule import SparsitySchedule
    from repro.marl import envs
    from repro.marl import train as T
    env = envs.get("predator_prey")
    tcfg = T.TrainConfig(batch=MARL_BATCH)
    sched = SparsitySchedule(groups=cfg.flgw_groups,
                             refresh_every=REFRESH_EVERY)
    log(f"A: IC3Net hidden={cfg.hidden} agents={ecfg.n_agents} "
        f"G={cfg.flgw_groups} ({cfg.flgw_path}) on predator_prey, "
        f"batch {MARL_BATCH}, {WINDOWS} windows x {WINDOW} iterations, "
        f"refresh every {REFRESH_EVERY}")
    _, compiled, compile_s = _compile_chunk(T._train_chunk, cfg, ecfg, tcfg,
                                            env, sched, seed)
    assert_kernel(compiled.as_text(), "A: _train_chunk")
    log(f"A: train chunk compiled in {compile_s:.3f} s (smoke output); "
        "tpu_custom_call present")
    _, grouped = _train_losses(cfg, ecfg, tcfg, env, sched, seed)
    log(f"A: grouped losses {np.array2string(grouped, precision=4)}")
    masked_cfg = dataclasses.replace(cfg, flgw_path="masked")
    _, masked = _train_losses(masked_cfg, ecfg, tcfg, env, sched, seed)
    log(f"A: masked  losses {np.array2string(masked, precision=4)}")
    np.testing.assert_allclose(grouped, masked, rtol=LOSS_RTOL,
                               atol=LOSS_ATOL)
    log(f"A: passed; max |grouped - masked| = "
        f"{np.max(np.abs(grouped - masked)):.4f}; peak_bytes_in_use "
        f"{peak_bytes()} (smoke output)")


# ---------------------------------------------------------------------------
# B. Kernels against their references
# ---------------------------------------------------------------------------

# f32 dots on the TPU run as bf16 passes by default (8-bit mantissa), and
# bf16 operands/outputs carry the same 8 bits; f32 accumulation keeps the
# error of a whole contraction near one rounding of the largest output.
MATMUL_TOL = 2e-2
# Attention gradients also sum bf16-rounded probabilities over thousands
# of positions and the query heads of each kv head.
FLASH_TOL, FLASH_GRAD_TOL = 2e-2, 5e-2


def phase_kernels(seed: int, *, ic3=(8, 128), lm=(4, 2304, 9216),
                  n_blocks: int = 13, heads=(8, 4, 256), seq: int = 4096,
                  window: int = 1024, softcap: float = 50.0,
                  groups: int = 4) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.flgw import FLGWConfig
    from repro.core.grouped import make_plan
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.flash_attention import ref as flash_ref
    from repro.kernels.flgw_matmul import ops as fops
    from repro.kernels.flgw_matmul import ref as fref
    from repro.kernels.plan_encode import ops as pe_ops
    slack = FLGWConfig().capacity_slack
    key = jax.random.PRNGKey(seed)
    log("B: kernels at real widths against ref.py (references at "
        "matmul precision 'highest')")

    b_lm, d_model, d_ff = lm
    cases = (("ic3net", ic3[0], ic3[1], ic3[1], jnp.float32),
             ("gemma2 mlp up", b_lm, d_model, d_ff, jnp.bfloat16),
             ("gemma2 mlp down", b_lm, d_ff, d_model, jnp.bfloat16))
    for i, (name, b, m, n, dtype) in enumerate(cases):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, (b, m), jnp.float32).astype(dtype)
        w = jax.random.normal(jax.random.fold_in(k, 1), (m, n),
                              jnp.float32).astype(dtype)
        ig = jax.random.normal(jax.random.fold_in(k, 2), (m, groups))
        og = jax.random.normal(jax.random.fold_in(k, 3), (groups, n))
        plan = make_plan(ig, og, slack)
        layout = (plan.row_ids, plan.col_ids, plan.row_valid, plan.col_valid)
        assert_kernel(fops.grouped_matmul.lower(x, w, *layout).compile()
                      .as_text(), f"grouped_matmul {name}")
        got = fops.grouped_matmul(x, w, *layout)
        wc = fops.compact_weights(w, *layout)
        cached = fops.grouped_matmul(x, w, *layout, wc)
        with jax.default_matmul_precision("highest"):
            want = fref.ref_grouped_matmul(x.astype(jnp.float32),
                                           w.astype(jnp.float32), *layout)
        check_close(f"grouped_matmul {name} ({b}x{m} -> {n}, "
                    f"{jnp.dtype(dtype).name})", got, want, MATMUL_TOL,
                    "bf16 passes/operands, f32 accumulation")
        check_close(f"serving consume path, cached wc, {name}", cached,
                    want, MATMUL_TOL, "as above")
        if not bool(jnp.array_equal(cached, got)):
            raise AssertionError(f"{name}: the cached-wc path differs from "
                                 "the per-call gather")
        log("  cached wc bitwise equal to the per-call gather")

    for lead, m in (((), 512), ((n_blocks,), d_ff)):
        scores = jax.random.normal(jax.random.fold_in(key, m),
                                   lead + (m, groups))
        assert_kernel(jax.jit(lambda s: pe_ops.balanced_assign(s, 1, slack))
                      .lower(scores).compile().as_text(),
                      f"balanced_assign M={m}")
        got = pe_ops.balanced_assign(scores, 1, slack)
        want = pe_ops.balanced_assign(scores, 1, slack, impl="reference")
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise AssertionError(f"balanced_assign M={m}: slots differ "
                                 "from the lexsort reference")
        log(f"  balanced_assign {lead + (m, groups)}: bitwise equal to the "
            "lexsort reference")

    hq, hkv, d = heads
    kq, kk, kv, kc = jax.random.split(jax.random.fold_in(key, 99), 4)
    q = jax.random.normal(kq, (1, hq, seq, d), jnp.float32).astype(
        jnp.bfloat16)
    k = jax.random.normal(kk, (1, hkv, seq, d), jnp.float32).astype(
        jnp.bfloat16)
    v = jax.random.normal(kv, (1, hkv, seq, d), jnp.float32).astype(
        jnp.bfloat16)
    ct = jax.random.normal(kc, q.shape, jnp.float32).astype(jnp.bfloat16)
    kw = dict(causal=True, window=window, softcap=softcap)

    def flash(q, k, v):
        return flash_ops.flash_attention(q, k, v, **kw)

    def flash_vjp(q, k, v, ct):
        out, vjp = jax.vjp(flash, q, k, v)
        return (out,) + vjp(ct)

    def ref_vjp(q, k, v, ct):
        out, vjp = jax.vjp(lambda *a: flash_ref.ref_attention(*a, **kw),
                           q, k, v)
        return (out,) + vjp(ct)

    fn = jax.jit(flash_vjp)
    assert_kernel(fn.lower(q, k, v, ct).compile().as_text(),
                  "flash_attention fwd+bwd")
    got = fn(q, k, v, ct)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_vjp)(*(a.astype(jnp.float32)
                                  for a in (q, k, v, ct)))
    shape = f"B=1 Hq={hq} Hkv={hkv} S={seq} D={d} window={window} " \
            f"softcap={softcap}"
    check_close(f"flash fwd ({shape})", got[0], want[0], FLASH_TOL,
                "bf16 output, probabilities in bf16 passes")
    for name, g, w_ in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        check_close(f"flash bwd {name}", g, w_, FLASH_GRAD_TOL,
                    "sums of bf16-rounded products over S positions")
    log(f"B: passed; peak_bytes_in_use {peak_bytes()} (smoke output)")


# ---------------------------------------------------------------------------
# C. Serving
# ---------------------------------------------------------------------------

# Both paths round bf16 activations at every layer, in different orders
# (tiled kernel accumulation vs one XLA dot), and the rounding compounds
# through the residual stream of the whole stack.
LOGITS_TOL = 5e-2


def phase_serving(cfg, seed: int, *, slots: int = 4, max_seq: int = 512,
                  prompt_len: int = 32, new_tokens: int = 16) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import kernels
    from repro.models import transformer
    from repro.serving import Engine, Request, ServeSession, plan_cache
    log(f"C: {cfg.name} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {jnp.dtype(cfg.dtype).name}) "
        f"FLGW G={cfg.flgw_groups} {cfg.flgw_path} on {cfg.flgw_targets}")
    t0 = time.perf_counter()
    params = jax.jit(lambda k: transformer.lm_init(k, cfg)[0])(
        jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"C: {n_params} params initialised in "
        f"{time.perf_counter() - t0:.3f} s; peak_bytes_in_use "
        f"{peak_bytes()} (smoke output)")

    plan_cache.clear()
    session = ServeSession(cfg, params, plan_policy="certify")
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (slots, prompt_len), dtype=np.int32)

    # The first decode step's logits: grouped kernels vs the jnp reference.
    cache = session.new_cache(slots, max_seq, per_slot=True)
    tok = jnp.asarray(prompts[:, :1])
    pos = jnp.zeros((slots, 1), jnp.int32)

    def first_logits(p, c, t, ps):
        return transformer.lm_apply(p, cfg, t, ps, cache=c, remat=False)[0]

    t0 = time.perf_counter()
    grouped_fn = jax.jit(first_logits).lower(params, cache, tok,
                                             pos).compile()
    log(f"C: decode forward compiled in {time.perf_counter() - t0:.3f} s "
        "(smoke output)")
    assert_kernel(grouped_fn.as_text(), "C: grouped decode forward")
    with kernels.use_reference_impl():
        ref_fn = jax.jit(lambda *a: first_logits(*a)).lower(
            params, cache, tok, pos).compile()
    if "tpu_custom_call" in ref_fn.as_text():
        raise AssertionError("C: the reference decode forward holds a kernel")
    got = grouped_fn(params, cache, tok, pos)
    want = ref_fn(params, cache, tok, pos)
    if got.shape != (slots, 1, cfg.vocab) or \
            not bool(jnp.isfinite(got).all()):
        raise AssertionError(f"C: bad logits {got.shape}")
    check_close("first decode step logits, grouped vs reference", got, want,
                LOGITS_TOL, "bf16 activations rounded per layer")
    log(f"C: peak_bytes_in_use {peak_bytes()} after the logits check "
        "(smoke output)")
    del cache, got, want, grouped_fn, ref_fn
    gc.collect()

    requests = [Request(rid=i, prompt=prompts[i], max_new_tokens=new_tokens)
                for i in range(slots)]
    engine = Engine(session, capacity=slots, max_seq=max_seq)
    report = engine.run(requests)
    for rec in report.records:
        if rec.completed < 0 or len(rec.tokens) != new_tokens or \
                not all(0 <= t < cfg.vocab for t in rec.tokens):
            raise AssertionError(f"C: request {rec.rid} incomplete: {rec}")
    stats = plan_cache.stats()
    if stats["encodes"] != 1:
        raise AssertionError(f"C: expected one plan encode, got {stats}")
    log(f"C: {len(report.records)} requests completed, "
        f"{report.generated_tokens} tokens in {report.steps} engine steps, "
        f"{report.wall_s:.3f} s wall incl. compile (smoke output); plan "
        f"cache {stats['encodes']} encode, {stats['hits']} hits")
    log(f"C: request 0 tokens {report.records[0].tokens}")
    log(f"C: passed; peak_bytes_in_use {peak_bytes()} (smoke output)")


# ---------------------------------------------------------------------------
# M. Four chips: the (env, agent) mesh
# ---------------------------------------------------------------------------

def phase_mesh(cfg, ecfg, seed: int, mesh_shape=(2, 2)) -> None:
    import jax
    import numpy as np
    from repro.core.schedule import SparsitySchedule
    from repro.launch.mesh import make_marl_mesh
    from repro.marl import envs
    from repro.marl import train as T
    env = envs.get("predator_prey")
    sched = SparsitySchedule(groups=cfg.flgw_groups,
                             refresh_every=REFRESH_EVERY)
    mesh = make_marl_mesh(env=mesh_shape[0], agent=mesh_shape[1])
    n_dev = mesh.devices.size
    tcfg_mesh = T.TrainConfig(batch=MARL_BATCH, mesh=mesh_shape)
    tcfg_one = T.TrainConfig(batch=MARL_BATCH)
    log(f"M: phase A's config on a {mesh_shape} (env, agent) mesh of "
        f"{n_dev} devices vs one chip, global batch {MARL_BATCH}")

    lowered, compiled, compile_s = _compile_chunk(
        T.make_mesh_chunk(mesh), cfg, ecfg, tcfg_mesh, env, sched, seed,
        contexts=T._mesh_contexts(mesh))
    hlo = compiled.as_text()
    axes = set(re.findall(r'sdy\.sharding_constraint[^\n]*\{"(env|agent)"\}',
                          lowered.as_text()))
    if axes != {"env", "agent"}:
        raise AssertionError(f"M: chunk not partitioned over both axes: "
                             f"{axes}")
    if "all-reduce" not in hlo:
        raise AssertionError("M: partitioned chunk holds no all-reduce")
    impl = ("Pallas kernels" if "tpu_custom_call" in hlo else
            "the jnp reference (use_reference_impl: GSPMD cannot "
            "partition a pallas_call)")
    log(f"M: mesh chunk compiled in {compile_s:.3f} s (smoke output); "
        f"FLGW layers on the mesh ran as {impl}")
    _, one_compiled, _ = _compile_chunk(T._train_chunk, cfg, ecfg, tcfg_one,
                                        env, sched, seed)
    assert_kernel(one_compiled.as_text(), "M: one-chip _train_chunk")
    log("M: FLGW layers on one chip ran as Pallas kernels")

    p_mesh, l_mesh = _train_losses(cfg, ecfg, tcfg_mesh, env, sched, seed)
    placed = {d for leaf in jax.tree.leaves(p_mesh)
              for d in leaf.sharding.device_set}
    if len(placed) != n_dev:
        raise AssertionError(f"M: params live on {len(placed)} device(s)")
    log(f"M: mesh losses     {np.array2string(l_mesh, precision=4)}; "
        f"params on {len(placed)} distinct devices")
    _, l_one = _train_losses(cfg, ecfg, tcfg_one, env, sched, seed)
    log(f"M: one-chip losses {np.array2string(l_one, precision=4)}")
    np.testing.assert_allclose(l_mesh, l_one, rtol=LOSS_RTOL,
                               atol=LOSS_ATOL)
    log(f"M: passed; max |mesh - one chip| = "
        f"{np.max(np.abs(l_mesh - l_one)):.4f}; peak_bytes_in_use "
        f"{peak_bytes()} on device 0 (smoke output)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the (env, agent) mesh MARL path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{compile_cache.enable()}")
    from repro.configs import registry
    from repro.marl import envs
    ic3 = dataclasses.replace(registry.get_config("ic3net"), flgw_groups=4,
                              flgw_path="grouped")
    _, ecfg = envs.make("predator_prey", n_agents=ic3.n_agents)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(ic3, ecfg, args.seed)
    else:
        phase_marl(ic3, ecfg, args.seed)
        gc.collect()
        phase_kernels(args.seed)
        gc.collect()
        phase_serving(registry.get_config(
            "gemma2_2b", flgw_groups=4, flgw_path="grouped",
            flgw_targets=("mlp",)), args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s "
        "(smoke output)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
