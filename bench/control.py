"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 bench/control.py --workload <cell> --seeds 1,2,... [--extra 3]

For each seed: the program's readings against the reference's (the lower
reading of each number is the largest of these). For the first ``extra``
seeds also the control (the reference in the program's place at the
precision below the configuration's) and each planted fault against the
reference: their smallest readings are the upper ones. Everything goes to
one JSON file under ``.bench_out/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--extra", type=int, default=3)
    a = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, harness
    run = harness.load_run(a.workload, 0, 0, False, T_START)
    harness.devices(run)
    runner = importlib.import_module(f"bench.runners.{run.config['runner']}")
    c, t = run.config, run.traffic
    rows = []
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        prog = runner.program_observables(c, t, seed)
        ref = runner.reference_observables(c, t, seed)
        ref.pop("taken")
        row = {"seed": seed, "program": check.training_numbers(prog, ref),
               "readings": {"program": prog, "reference": ref}}
        if i < a.extra:
            ctl = runner.reference_observables(c, t, seed, **runner.CONTROL)
            row["control"] = check.training_numbers(ctl, ref)
            for name, variant in runner.faults(t).items():
                f = runner.reference_observables(c, t, seed, **variant)
                row[name] = check.training_numbers(f, ref)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        harness.log(json.dumps({k: v for k, v in row.items() if k != "readings"}))
    os.makedirs(harness.OUT, exist_ok=True)
    path = os.path.join(harness.OUT, f"control-{a.workload}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    # the lower reading is the program's largest, an upper one the
    # smallest that the control or a fault gives
    summary = {}
    for kind in ["program", "control", *runner.faults(t)]:
        got = [r[kind] for r in rows if kind in r]
        pick = max if kind == "program" else min
        summary[kind] = {k: pick(g[k] for g in got)
                         for k in got[0] if not k.startswith("_")}
    print(json.dumps({"workload": a.workload, "summary": summary,
                      "seconds": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
