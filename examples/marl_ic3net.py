"""End-to-end driver: IC3Net + FLGW sparse training on any registered env.

The paper's own workload (§IV-A) is Predator-Prey; ``--env`` selects any
scenario from the ``repro.marl.envs`` registry (Traffic Junction and
cooperative-navigation Spread ship alongside it). Training runs fully on
device — whole log windows execute as one ``jax.lax.scan`` — with optional
dense warmup before the FLGW mask switches on (``--warmup``) and optional
scale-out over a 2-D ``(env, agent)`` ``jax.sharding`` mesh (``--mesh``;
the old ``--parallel`` pmap switch survives as a deprecated alias).
Prints the mesh sharding spec, the success-rate curve and the sparsity
actually realised by the learned grouping matrices.

  PYTHONPATH=src python examples/marl_ic3net.py --env traffic_junction \
      --agents 4 --groups 4 --iterations 200
  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python examples/marl_ic3net.py --mesh 2,2 --agents 4 --batch 16
"""
import argparse

import numpy as np

from repro import compile_cache
from repro.core import flgw
from repro.core.schedule import SparsitySchedule
from repro.marl import envs as envs_mod
from repro.marl import ic3net
from repro.marl import train as train_mod


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="predator_prey",
                    choices=envs_mod.names())
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--size", type=int, default=4)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--path", default="masked",
                    choices=("masked", "grouped"))
    ap.add_argument("--iterations", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--warmup", type=int, default=0,
                    help="train dense for this many iterations before "
                         "enabling the FLGW mask")
    ap.add_argument("--refresh", type=int, default=1,
                    help="re-encode the grouped path's plan cache every k "
                         "iterations (OSEL amortization; 1 = every step)")
    ap.add_argument("--refresh-mode", default="period",
                    choices=("period", "on_change", "hybrid"),
                    help="plan-refresh policy: fixed period, or "
                         "change-driven from the ig/og argmax hash "
                         "(repro.core.encoder)")
    ap.add_argument("--mesh", default=None,
                    help="ENV,AGENT shard counts of the jax.sharding mesh "
                         "path (e.g. 2,2); 'auto' puts every local device "
                         "on the env axis. --batch stays the GLOBAL env "
                         "batch. Replaces --parallel.")
    ap.add_argument("--parallel", action="store_true",
                    help="DEPRECATED: routes to --mesh auto (the old pmap "
                         "path is retired)")
    ap.add_argument("--log-every", type=int, default=0,
                    help="log-window length (0 = iterations/10); the scan "
                         "path runs one on-device window per log line")
    ap.add_argument("--host-loop", action="store_true",
                    help="drive one update per host iteration (seed loop) "
                         "instead of the on-device scan")
    args = ap.parse_args(argv)

    cfg = ic3net.IC3NetConfig(hidden=args.hidden, flgw_groups=args.groups,
                              flgw_path=args.path)
    env, ecfg = envs_mod.make(args.env, n_agents=args.agents,
                              size=args.size, max_steps=3 * args.size)
    mesh_shape = None
    if args.mesh:
        from repro.launch.mesh import parse_marl_mesh
        try:
            mesh_shape = ((0, 1) if args.mesh == "auto"
                          else parse_marl_mesh(args.mesh))
        except ValueError as e:
            ap.error(str(e))
    tcfg = train_mod.TrainConfig(batch=args.batch, parallel=args.parallel,
                                 mesh=mesh_shape)
    if mesh_shape is not None:
        from repro.launch.mesh import describe_marl_mesh, make_marl_mesh
        print(describe_marl_mesh(
            make_marl_mesh(env=mesh_shape[0], agent=mesh_shape[1]),
            batch=args.batch, n_agents=args.agents))
    schedule = SparsitySchedule(groups=args.groups,
                                warmup_steps=args.warmup,
                                refresh_every=args.refresh,
                                refresh=args.refresh_mode) \
        if (args.warmup or args.refresh > 1
            or args.refresh_mode != "period") else None
    print(f"IC3Net on {args.env} A={args.agents} hidden={args.hidden} "
          f"FLGW G={args.groups} ({args.path}) "
          f"-> expected sparsity {100 * (1 - 1 / max(args.groups, 1)):.1f}%"
          + (f", dense warmup {args.warmup} iters" if args.warmup else ""))

    params, hist = train_mod.train(
        cfg, ecfg, tcfg, args.iterations, seed=args.seed,
        log_every=args.log_every or max(1, args.iterations // 10), env=env,
        schedule=schedule, host_loop=args.host_loop)
    succ = np.array([h["success"] for h in hist])
    k = max(1, len(succ) // 10)
    print(f"success: first-{k} {succ[:k].mean():.3f}  "
          f"last-{k} {succ[-k:].mean():.3f}")
    # throughput from inside the scan (skip the compile-heavy first window)
    tail = hist[len(hist) // 2:]
    print(f"throughput: {np.mean([h['steps_per_s'] for h in tail]):.2f} "
          f"iters/s, {np.mean([h['env_steps_per_s'] for h in tail]):.0f} "
          f"env-steps/s, est. sparse "
          f"{np.mean([h['sparse_gflops'] for h in tail]):.3f} GFLOPS")

    if args.groups > 1:
        # realised sparsity of each learned FLGW layer
        print("learned per-layer sparsity:")
        for name, p in params.items():
            if isinstance(p, dict) and "ig" in p:
                ig_idx, og_idx = flgw.grouping_indices(p["ig"], p["og"])
                s = float(flgw.mask_sparsity(ig_idx, og_idx,
                                             groups=args.groups))
                print(f"  {name:<8} {100 * s:.1f}%")


if __name__ == "__main__":
    main()
