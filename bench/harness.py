"""What every cell's run shares: the manifest, the platform check, seeds,
per-layer metric readers and the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, ".bench_out")


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """One run of one cell."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                 # process start on time.perf_counter()
    require_tpu: bool = True
    out_dir: str = OUT

    @property
    def name(self) -> str:
        return self.cell["name"]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def load_run(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> Run:
    m = manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in m["configs"]}[cell["config"]]
    config = _json(os.path.join(ROOT, conf["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
    return Run(cell, config, traffic, seed, seconds, trace, t_start)


def devices(run: Run):
    """The devices the cell runs on; fails unless they are accelerators
    of a kind with published peaks, as many as the cell asks for."""
    import jax
    from bench import peaks
    devs = jax.devices()
    if run.require_tpu:
        if devs[0].platform != "tpu":
            raise NoAccelerator(f"no TPU: JAX found {devs[0].platform}")
        peaks.peaks(devs[0].device_kind)
    want = run.cell["chips"]
    if len(devs) < want:
        raise NoAccelerator(f"cell needs {want} chips, JAX found {len(devs)}")
    return devs[:want]


def seed_key(seed: int):
    """A PRNG key from any whole seed, 64-bit ones included."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def leaf_norms(tree) -> dict:
    """Norm of each leaf of a pytree, in float32, keyed by its path."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.linalg.norm(x.astype(jnp.float32)), t))(tree)
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(norms)[0]}


def memory_peak(devs) -> int:
    """Peak device memory of the fullest chip: the buffers the runtime
    counts in use plus what it reserves for compiled programs' temporaries,
    which ``peak_bytes_in_use`` leaves out."""
    stats = [d.memory_stats() or {} for d in devs]
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


def device_info(devs, peak: int, busy_s=None, window_s=None) -> dict:
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
    if busy_s is not None:
        info["busy_s"] = busy_s
        info["window_s"] = window_s
    return info


def per_layer(run: Run, context: dict) -> dict:
    """Each per-layer metric of this cell, read by its own reader
    ``bench/metrics/<name>.py``; a reader that finds nothing returns None
    and the metric is left out."""
    out = {}
    for metric in manifest()["per_layer"]:
        if run.name not in metric.get("workloads", [run.name]):
            continue
        path = os.path.join(BENCH, "metrics", f"{metric['name']}.py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(context)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def end_to_end(run: Run, values: dict) -> dict:
    out = {}
    for metric in manifest()["end_to_end"]:
        if run.name in metric.get("workloads", [run.name]):
            out[metric["name"]] = {"value": float(values[metric["name"]]),
                                   "unit": metric["unit"]}
    return out


def execute(run: Run, devs, program, reference, work: dict, rates) -> dict:
    """The measured part of a run, the same for every runner.

    ``program`` is the compiled step with its state, brought up from the
    seed; this function holds the only reference to it. Its
    ``first_steps()`` drives the compared first steps and returns what the
    reference is compared with; its ``window_call()`` runs one unit of the
    window (a dispatch of updates) and returns the loss of each
    update it ran, fetched to the host. Without ``--trace``, units run
    back to back until ``seconds`` have passed and ``rates(updates,
    elapsed)`` gives the end-to-end values; with it,
    ``trace_calls`` units run under the profiler and the per-layer readers
    get the reduced trace with ``work`` (the operations one update needs,
    from bench.flops). Then the program is freed, ``reference()`` computes
    the reference's readings and ``correct`` is decided.
    """
    import gc
    import jax
    from bench import check, peaks, trace
    observed = program.first_steps()
    call = program.window_call
    setup_s = since(run.t_start)
    log(f"setup {setup_s:.3f} s; first losses {observed['loss']}")
    losses, busy, breakdown = [], (None, None), None
    if run.trace:
        d = os.path.join(run.out_dir, "traces", f"{run.name}-{run.seed}")
        with trace.capture(d):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(run.traffic["trace_calls"]):
                    with jax.profiler.TraceAnnotation("bench.call"):
                        losses.extend(call())
        reduced = trace.load(d)
        trace.save(reduced, os.path.join(d, "reduced.json.gz"))
        lo, hi = trace.window(reduced)
        ids = sorted(reduced["devices"], key=int)[:len(devs)]
        busy = (sum(trace.busy_s(reduced, i, lo, hi) for i in ids) / len(ids),
                hi - lo)
        breakdown = trace.breakdown(reduced, ids[0], lo, hi)
        context = {"trace": reduced, "device": ids[0], "window": (lo, hi),
                   "updates": len(losses), "chips": len(devs),
                   "peaks": peaks.peaks(devs[0].device_kind), "work": work,
                   "config": run.config, "traffic": run.traffic}
    else:
        t0 = time.perf_counter()
        while True:
            losses.extend(call())
            now = time.perf_counter()
            if now - t0 >= run.seconds:
                break
        values = dict(rates(len(losses), now - t0), setup_s=setup_s)
    peak = memory_peak(devs)
    failed = sum(1 for x in losses if not math.isfinite(x))
    del program, call
    gc.collect()

    numbers = check.training_numbers(observed, reference())
    log("numbers", numbers)
    correct, checks = check.verdict(numbers, check.limits_for(run.name))
    metrics = per_layer(run, context) if run.trace else end_to_end(run, values)
    line = {"correct": correct and failed == 0, "attempted": len(losses),
            "failed": failed, "metrics": metrics,
            "device": device_info(devs, peak, *busy)}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    check.report(checks)
    return line


def since(t0: float) -> float:
    return time.perf_counter() - t0


def log(*args) -> None:
    print("[bench]", *args, file=sys.stderr, flush=True)
