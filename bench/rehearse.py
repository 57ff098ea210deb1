"""Rehearse every cell without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell>] [--skip-compile]

1. Compiles, for a described TPU v5e (no chip attached), the programs each
   cell puts on the chip at its real shapes, and prints their memory
   analysis: what the chip's compiler would refuse shows here.
2. Runs each cell's command on this machine's CPU, where it has to exit
   non-zero at the platform check and print no result.
"""
import argparse
import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_cell(name: str) -> dict:
    import jax
    from jax.experimental import topologies
    from bench import harness
    run = harness.load_run(name, 0, 0, False, 0.0)
    runner = importlib.import_module(f"bench.runners.{run.config['runner']}")
    # The kernels ask the backend whether to interpret; compiling for the
    # described chip needs them compiled.
    import repro.core.grouped as g
    import repro.kernels.flgw_matmul.ops as fo
    import repro.kernels.plan_encode.ops as po
    for mod in (g, fo, po):
        if hasattr(mod, "default_interpret"):
            mod.default_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    compiled = runner.rehearse(run.config, run.traffic,
                            topo.devices[:run.cell["chips"]])
    out = {}
    for what, exe in compiled.items():
        m = exe.memory_analysis()
        out[what] = {"argument_bytes": m.argument_size_in_bytes,
                     "output_bytes": m.output_size_in_bytes,
                     "alias_bytes": m.alias_size_in_bytes,
                     "temp_bytes": m.temp_size_in_bytes,
                     "kernels": exe.as_text().count("tpu_custom_call")}
    return out


def refuses_cpu(name: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", name, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    return {"exit": p.returncode, "stdout_lines": len(p.stdout.splitlines()),
            "refused": p.returncode != 0 and not p.stdout.strip()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append")
    ap.add_argument("--skip-compile", action="store_true")
    a = ap.parse_args()
    from bench import harness
    names = a.workload or [w["name"] for w in harness.manifest()["workloads"]]
    report, ok = {}, True
    for name in names:
        report[name] = {"cpu run": refuses_cpu(name)}
        ok = ok and report[name]["cpu run"]["refused"]
        if not a.skip_compile:
            report[name]["described v5e compile"] = compile_cell(name)
    print(json.dumps(report, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
