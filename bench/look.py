"""Where the program and the reference part, update by update; not part of
a run.

    python3 bench/look.py --workload <cell> --seeds 1,2,...

Both sample their actions and gates from the same keys. For each seed the
program runs the compared updates one update a dispatch, and before each
update its own rollout (``repro.marl.train.rollout``, ``collect=True``)
gives the actions and gates that update samples. The reference runs twice:
sampling its own, and taking the program's. Printed per seed: the share of
actions and of gates that differ, and of envs with any that differs, per
update; each update's loss gap, sampled and taken; and the numbers of
``correct`` for the program (one update a dispatch and the cell's own
dispatch) against the reference, sampled and taken. Everything goes to one
JSON file under ``.bench_out/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def program_by_update(c: dict, t: dict, seed: int) -> tuple[dict, tuple]:
    """The program's readings with one update a dispatch, and the actions
    and gates of each update, each ``(updates, batch, steps, agents)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import harness
    from bench.runners import marl
    from repro.marl import train as mt
    prog = marl.Program(c, t, seed)
    cfg, ecfg, tcfg, env, schedule = prog.statics

    @jax.jit
    def decisions(params, key, plans):
        _, k = jax.random.split(key)
        outs = jax.vmap(lambda kk: mt.rollout(params, kk, cfg, ecfg, env,
                                              plans, collect=True))(
            jax.random.split(k, tcfg.batch))
        return outs[7], outs[5]          # action, new_gate

    updates = marl.compared_dispatches(t) * t["window_updates"]
    actions, gates, losses, grad = [], [], [], None
    for u in range(updates):
        with jax.default_matmul_precision(prog.precision):
            a, g = decisions(prog.params, prog.key, prog.plans)
            (prog.params, prog.opt, prog.key, prog.plans,
             metrics) = mt._train_chunk(prog.params, prog.opt, prog.key,
                                        prog.plans, jnp.asarray(u, jnp.int32),
                                        1, *prog.statics)
        actions.append(np.asarray(a))
        gates.append(np.asarray(g))
        losses.extend(np.asarray(metrics["loss"]).tolist())
        if u + 1 == t["window_updates"]:
            grad = harness.leaf_norms(jax.tree.map(jnp.sqrt, prog.opt))
    delta = harness.leaf_norms(jax.tree.map(jnp.subtract, prog.params,
                                            prog.params0))
    readings = {"loss": losses[:t["compare_updates"]], "losses": losses,
                "grad": grad, "delta": delta}
    return readings, (np.stack(actions), np.stack(gates))


def differ(mine, theirs) -> dict:
    """Per update: share of decisions, and of envs with any, that differ."""
    import numpy as np
    d = np.asarray(mine) != np.asarray(theirs)
    return {"decisions": d.mean(axis=(1, 2, 3)).tolist(),
            "envs": d.any(axis=(2, 3)).mean(axis=1).tolist()}


def look(c: dict, t: dict, seed: int) -> dict:
    import numpy as np
    from bench import check
    from bench.runners import marl
    by_update, (actions, gates) = program_by_update(c, t, seed)
    own = marl.program_observables(c, t, seed)
    sampled = marl.reference_observables(c, t, seed)
    taken = marl.reference_observables(c, t, seed, forced=(actions, gates))
    ra, rg = (np.asarray(x) for x in sampled.pop("taken"))
    taken.pop("taken")

    def gaps(p, r):
        return [check.relative(a, b) for a, b in zip(p["losses"], r["losses"])]
    return {"seed": seed,
            "actions_differ": differ(actions, ra),
            "gates_differ": differ(gates, rg),
            "loss_gap_sampled": gaps(by_update, sampled),
            "loss_gap_taken": gaps(by_update, taken),
            "cell_dispatch_vs_sampled": check.training_numbers(own, sampled),
            "by_update_vs_sampled": check.training_numbers(by_update, sampled),
            "by_update_vs_taken": check.training_numbers(by_update, taken),
            "cell_dispatch_vs_by_update": check.training_numbers(own, by_update)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    run = harness.load_run(a.workload, 0, 0, False, T_START)
    harness.devices(run)
    rows = []
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        row = look(run.config, run.traffic, seed)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        harness.log(json.dumps(row))
    os.makedirs(harness.OUT, exist_ok=True)
    with open(os.path.join(harness.OUT, f"look-{a.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps({"workload": a.workload, "seeds": len(rows),
                      "seconds": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
