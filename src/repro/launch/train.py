"""LM training launcher: mesh + sharded init + data + fault-tolerant loop.

The production entry point (and the end-to-end driver the examples call):

  PYTHONPATH=src python -m repro.launch.train --arch gemma2_2b --smoke \
      --steps 50 --batch 8 --seq 256 --flgw-groups 4

On the CPU container this runs the reduced (smoke) configs; on a real
fleet the same file runs the full config on the production mesh — the only
difference is ``--smoke`` and the device set jax reports.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import registry
from repro.core.schedule import SparsitySchedule
from repro.data.pipeline import SyntheticTokens, make_batch_iterator
from repro.launch.mesh import make_mesh_from_devices
from repro.runtime.fault import PreemptionGuard, StepRunner
from repro.sharding import partition
from repro.train import state as state_lib
from repro.train import step as step_lib


def train_lm(arch: str, *, smoke: bool = True, steps: int = 20,
             batch: int = 8, seq: int = 256, lr: float = 3e-4,
             flgw_groups: int = 1, flgw_path: str = "masked",
             refresh_every: int = 1, refresh: str = "period",
             optimizer: str = "adamw", ckpt_dir: str = None,
             save_every: int = 100, log_every: int = 10,
             banded: bool = False, seed: int = 0):
    get = registry.get_smoke_config if smoke else registry.get_config
    overrides = {}
    if flgw_groups > 1:
        overrides = dict(flgw_groups=flgw_groups, flgw_path=flgw_path)
    cfg = get(arch, **overrides)
    # plan-refresh schedule for the grouped path (the decoder stack shares
    # the MARL engine's encoder subsystem; see repro.core.encoder)
    schedule = None
    if flgw_groups > 1 and flgw_path == "grouped" and \
            (refresh_every > 1 or refresh != "period"):
        schedule = SparsitySchedule(groups=flgw_groups,
                                    refresh_every=refresh_every,
                                    refresh=refresh)

    mesh = make_mesh_from_devices()
    specs = state_lib.state_specs(cfg, optimizer=optimizer)
    abstract = state_lib.abstract_state(cfg, optimizer=optimizer)
    state_sh = partition.constrained_shardings(specs, abstract, mesh)
    batch_sh = {k: partition.batch_sharding(mesh, 2)
                for k in ("tokens", "targets", "positions")}

    with mesh, partition.use_constraints(mesh):
        init = jax.jit(
            lambda k: state_lib.init_state(k, cfg, optimizer=optimizer),
            out_shardings=state_sh)
        state = init(jax.random.PRNGKey(seed))

        step_fn = jax.jit(
            step_lib.make_train_step(cfg, optimizer=optimizer, lr=lr,
                                     banded=banded, schedule=schedule),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None), donate_argnums=(0,))

        ds = SyntheticTokens(cfg.vocab, batch, seq, seed=seed)
        runner = None
        start = 0
        if ckpt_dir:
            runner = StepRunner(step_fn, ckpt_dir, save_every=save_every)
            # Plans-aware restore: migrates pre-plans grouped manifests and
            # re-encodes TrainState.plans from the restored params, so the
            # resumed step is bitwise-identical under any refresh mode.
            state, start = runner.restore_or(
                state, shardings=state_sh,
                restore_fn=lambda s, sh: state_lib.restore_state(
                    ckpt_dir, s, cfg, shardings=sh))
        batches = make_batch_iterator(ds, start_step=start,
                                      sharding=batch_sh)

        t0 = time.time()
        if runner is not None:
            state, end, history = runner.run(
                state, batches, start_step=start, max_steps=steps,
                log_every=log_every)
        else:
            history = []
            end = start
            for b in batches:
                if end >= steps:
                    break
                state, metrics = step_fn(state, b)
                end += 1
                history.append(metrics)
                if log_every and end % log_every == 0:
                    print(f"step {end}: loss="
                          f"{float(metrics['loss']):.4f}", flush=True)  # noqa: ANL002 — log_every-gated print; fetch is the point
        dt = time.time() - t0

    losses = [float(h["loss"]) for h in history]
    print(f"{arch}: steps {start}->{end} in {dt:.1f}s  "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"
          if losses else f"{arch}: no steps run")
    return state, history


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=[a for a in registry.ARCH_IDS if a != "ic3net"])
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--flgw-groups", type=int, default=1)
    ap.add_argument("--flgw-path", default="masked",
                    choices=("masked", "grouped"))
    ap.add_argument("--refresh", type=int, default=1,
                    help="re-encode the grouped path's plan cache every k "
                         "steps (OSEL amortization; 1 = every step)")
    ap.add_argument("--refresh-mode", default="period",
                    choices=("period", "on_change", "hybrid"),
                    help="plan-refresh policy (see repro.core.encoder)")
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "rmsprop"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--banded", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    train_lm(a.arch, smoke=a.smoke, steps=a.steps, batch=a.batch,
             seq=a.seq, lr=a.lr, flgw_groups=a.flgw_groups,
             flgw_path=a.flgw_path, refresh_every=a.refresh,
             refresh=a.refresh_mode, optimizer=a.optimizer,
             ckpt_dir=a.ckpt_dir, save_every=a.save_every,
             log_every=a.log_every, banded=a.banded, seed=a.seed)


if __name__ == "__main__":
    main()
