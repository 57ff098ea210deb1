"""Device time of the MARL step by layer, and the host's work in each idle
gap, from the raw profiler trace of a traced window.

Extends ``bench/trace.py``, whose reduced form keeps only the benchmark's
own host spans. Here the raw ``.xplane.pb`` beside the newest
``reduced.json.gz`` is read again (``load``) for device 0's operations and
programs and for four kinds of host event that JAX's runtime records:

- ``tpu::System::Execute``, the launch of a program, and
  ``tpu::System::Execute=>Done``, the host learning that it finished;
- ``np.asarray(jax.Array)``, a fetch of a result to the host;
- ``PjitFunction(<fn>)``, the Python dispatch of a jitted call.

The layer of a device operation comes from the program: the step names
its layers with ``jax.named_scope`` (``repro.scopes.LAYER_SCOPES``), and
the compiled chunk's HLO text gives each instruction its ``op_name``
(``scope_map``). An operation belongs to the innermost layer on that path,
and to the backward when the path holds ``transpose(``.

The host's and the device's clocks differ by up to about a millisecond, and
from run to run. ``align`` pairs each device program, in ``run_id`` order,
with its launch, and shifts the device clock by the smallest offset that
starts no program before its launch; each completion bounds the offset
from above.

Everything after ``load`` and ``scope_map`` works on a plain form (``form``),
so it can be checked on a recorded trace without a chip.
"""
from __future__ import annotations

import glob
import json
import os
import re

from bench import harness, trace

LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
FETCH = "np.asarray(jax.Array)"
DISPATCH = "PjitFunction("
# Instructions that hold others: their time is their children's.
CONTAINERS = ("while", "conditional", "call")

POLICY = ("policy", "comm", "encoder", "lstm", "heads")
DEVICE_METRICS = ("env_ms.marl", "policy_fwd_ms.marl", "policy_bwd_ms.marl",
                  "sample_ms.marl", "loss_optim_ms.marl")


# --- reading ---------------------------------------------------------------

def raw_trace(out_dir: str = harness.OUT) -> str | None:
    """The ``.xplane.pb`` beside the newest ``reduced.json.gz``."""
    reduced = glob.glob(os.path.join(out_dir, "traces", "*",
                                     "reduced.json.gz"))
    if not reduced:
        return None
    d = os.path.dirname(max(reduced, key=os.path.getmtime))
    files = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, device: str = "0") -> dict:
    """The plain form of a raw trace, without its scope map: the window,
    the device's operations ``[instruction, start, dur]`` and programs
    ``[name, start, dur, run_id]`` (device clock), and the host's events
    (host clock). Seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    form = {"ops": [], "modules": [],
            "host": {"launch": [], "done": [], "fetch": [], "dispatch": []}}
    window = None
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and m.group(1) == device:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    form["ops"].extend(
                        [trace.op_name(e.name), _s(e.start_ns), _s(e.duration_ns)]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    form["modules"].extend(
                        [e.name, _s(e.start_ns), _s(e.duration_ns),
                         int(dict(e.stats).get("run_id", -1))]
                        for e in line.events)
        elif plane.name.startswith("/host"):
            host = form["host"]
            for line in plane.lines:
                for e in line.events:
                    span = [_s(e.start_ns), _s(e.duration_ns)]
                    if e.name == LAUNCH:
                        host["launch"].append(span)
                    elif e.name == DONE:
                        host["done"].append(span)
                    elif e.name == FETCH:
                        host["fetch"].append(span)
                    elif e.name.startswith(DISPATCH):
                        host["dispatch"].append([e.name] + span)
                    elif e.name == "bench.window":
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    if window is None:
        raise KeyError(f"no bench.window in {path}")
    form["window"] = list(window)
    form["ops"].sort(key=lambda e: e[1])
    form["modules"].sort(key=lambda e: e[3])
    for spans in form["host"].values():
        spans.sort(key=lambda e: e[-2])
    return form


def _s(ns) -> float:
    return round(ns * 1e-9, 9)


# --- the scope map ---------------------------------------------------------

INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def chunk_text(config: dict, traffic: dict) -> str:
    """HLO text of the MARL chunk compiled as ``bench/runners/marl.py``
    dispatches it: the same statics, single-device shapes and matmul
    precision.

    The persistent cache's key leaves metadata out by default, so an entry
    compiled from other code (without scopes) would answer; the key here
    holds the metadata, and JAX's in-memory caches, which may hold the
    executable the window ran, are cleared first."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from bench.reference import ic3net as ref
    from bench.runners import marl
    from repro.marl import ic3net
    from repro.marl import train as mt
    from repro.optim.optimizers import rmsprop_init

    statics = marl._program_config(config, traffic)
    sharding = SingleDeviceSharding(jax.devices()[0])

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: ref.init_params(k, config), key)
    plans = jax.eval_shape(lambda p: ic3net.encode_plans(p, statics[0]),
                           params)
    args = spec((params, jax.eval_shape(rmsprop_init, params),
                 jax.eval_shape(lambda: key), plans,
                 jax.ShapeDtypeStruct((), jnp.int32)))
    jax.clear_caches()
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        with jax.default_matmul_precision(config["matmul_precision"]):
            compiled = mt._train_chunk.lower(
                *args, traffic["window_updates"], *statics).compile()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
    return compiled.as_text()


def scope_map(text: str) -> dict:
    """Instruction name -> [opcode, op_name] ('' where it has none)."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(line)
            out[m.group(1)] = [m.group(2), op.group(1) if op else ""]
    return out


def layer_of(op_name: str, layers) -> str | None:
    """Innermost entry of ``layers`` on an ``op_name`` path; a component
    may wrap its scope in transforms (``jvp(vmap(rollout))``)."""
    found = None
    for part in op_name.split("/"):
        while True:
            m = re.fullmatch(r"[\w.\-]*\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
        if part in layers:
            found = part
    return found


def bucket(layer: str | None, backward: bool) -> str | None:
    """The metric an operation of ``layer`` counts toward; None for the
    unscoped rest (``rollout`` bookkeeping, ``plan_refresh``, unnamed)."""
    if layer == "env":
        return "env_ms.marl"
    if layer in POLICY:
        return "policy_bwd_ms.marl" if backward else "policy_fwd_ms.marl"
    if layer == "sample":
        return "sample_ms.marl"
    if layer in ("a2c", "rmsprop"):
        return "loss_optim_ms.marl"
    return None


# --- alignment and reductions ---------------------------------------------

def align(form: dict) -> tuple[float, float]:
    """(offset, bound): device time + offset is host time. The offset is
    the smallest that starts no program before its launch; no program may
    end after the host learned it was done, so the offset must stay at or
    under the bound."""
    mods, launch, done = (form["modules"], form["host"]["launch"],
                          form["host"]["done"])
    if not mods or len(launch) != len(mods) or len(done) != len(mods):
        raise ValueError(f"{len(mods)} programs, {len(launch)} launches and "
                         f"{len(done)} completions: cannot pair them")
    offset = max(ln[0] - m[1] for m, ln in zip(mods, launch))
    bound = min(dn[0] - (m[1] + m[2]) for m, dn in zip(mods, done))
    return offset, bound


def overlap(a, b) -> float:
    """Length of the intersection of two lists of merged (start, end)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def module_base(name: str) -> str:
    """``jit__scan_chunk(8717...)`` -> ``jit__scan_chunk``."""
    return name.split("(", 1)[0]


def analyse(form: dict) -> dict:
    """Every number the readers report, and under ``_detail`` what the log
    shows, from the plain form with its scope map, layer names and update
    count. Without layer names only the idle pair is reported."""
    offset, bound = align(form)
    layers = form["layers"]
    lo, hi = form["window"]
    scopes = form["scopes"]
    updates = form["updates"]

    # the chunk: the program with the most device time in the window
    time_of: dict[str, float] = {}
    for name, s, d, _ in form["modules"]:
        s += offset
        time_of[module_base(name)] = (time_of.get(module_base(name), 0.0)
                                      + max(0.0, min(s + d, hi) - max(s, lo)))
    chunk = max(time_of, key=time_of.get)
    runs = [(s + offset, s + d + offset) for name, s, d, _ in form["modules"]
            if module_base(name) == chunk and s + offset < hi
            and s + d + offset > lo]

    ops = [(n, s + offset, d) for n, s, d in form["ops"]]
    leaves, mapped = [], 0.0
    by_layer: dict[tuple, float] = {}      # (layer, backward) -> seconds
    k = 0
    for n, s, d in ops:
        mid = s + d / 2
        while k < len(runs) and runs[k][1] < mid:
            k += 1
        if k == len(runs):
            break
        if mid < runs[k][0] or not lo <= mid <= hi:
            continue
        opcode, op_name = scopes.get(n, (None, ""))
        if opcode in CONTAINERS:
            continue
        leaves.append((n, s, d))
        if opcode is not None:
            mapped += d
        key = (layer_of(op_name, layers), "transpose(" in op_name)
        by_layer[key] = by_layer.get(key, 0.0) + d
    seconds = dict.fromkeys(DEVICE_METRICS, 0.0)
    for (layer, backward), v in by_layer.items():
        metric = bucket(layer, backward)
        if metric is not None:
            seconds[metric] += v
    busy = trace.covered(trace.union(leaves, lo, hi))
    out = {m: 1e3 * v / updates for m, v in seconds.items()}
    out["unscoped_ms.marl"] = 1e3 * (busy - sum(seconds.values())) / updates
    leaf_s = sum(by_layer.values())

    def layer_ms(scope):
        return 1e3 * sum(v for (layer, _), v in by_layer.items()
                         if layer == scope) / updates

    # idle stretches of the window on the aligned clock, by host activity
    gaps = trace.idle_gaps({"devices": {"0": {"ops": ops}}}, "0", lo, hi)
    host = form["host"]
    fetch = trace.union([("", s, d) for s, d in host["fetch"]], lo, hi)
    pjit = trace.union(host["dispatch"], lo, hi)
    launch = f"PjitFunction({re.sub(r'^jit_', '', chunk)})"
    launch_pjit = trace.union([e for e in host["dispatch"] if e[0] == launch],
                              lo, hi)
    dispatches = len(runs)
    idle = trace.covered(gaps)
    out["idle_fetch_ms.marl"] = 1e3 * overlap(gaps, fetch) / dispatches
    out["idle_dispatch_ms.marl"] = 1e3 * overlap(gaps, pjit) / dispatches
    out["_detail"] = {
        "offset_ms": 1e3 * offset, "offset_bound_ms": 1e3 * bound,
        "chunk": chunk, "dispatches": dispatches, "updates": updates,
        "busy_ms_per_update": 1e3 * busy / updates,
        "idle_ms_per_dispatch": 1e3 * idle / dispatches,
        "idle_chunk_launch_ms_per_dispatch":
            1e3 * overlap(gaps, launch_pjit) / dispatches,
        "mapped_share": mapped / leaf_s if leaf_s else 0.0,
        "leaf_ops": len(leaves),
        # what ``unscoped`` holds, by the scope that names it
        "rollout_ms_per_update": layer_ms("rollout"),
        "plan_refresh_ms_per_update": layer_ms("plan_refresh"),
        "unnamed_ms_per_update": layer_ms(None)}
    if not layers:
        for m in DEVICE_METRICS + ("unscoped_ms.marl",):
            del out[m]
    return out


# --- the readers' entry ----------------------------------------------------

_CACHE: dict = {}


def metrics(ctx: dict) -> dict:
    """The layer metrics of the traced window in ``ctx``, computed once
    per window; {} where they cannot be read, with the reason in the log.
    The device layers need the program's scopes: a program without
    ``repro.scopes.LAYER_SCOPES`` gives only the idle pair."""
    key = tuple(ctx["window"])
    if key not in _CACHE:
        try:
            form = form_of(ctx)
            out = analyse(form)
        except Exception as e:  # noqa: BLE001 — a reader never raises
            harness.log(f"layer metrics not read: {type(e).__name__}: {e}")
            out = {"_detail": None}
        detail = out.pop("_detail")
        if detail is not None:
            lo, hi = form["window"]
            detail["traced_env_steps_per_s"] = (
                ctx["updates"] * ctx["traffic"]["batch"]
                * ctx["config"]["max_steps"] / (hi - lo))
            detail.update(form.get("notes", {}))
            harness.log("layers", json.dumps(dict(out, **detail)))
        _CACHE[key] = out
    return _CACHE[key]


def form_of(ctx: dict) -> dict:
    """The plain form of the run's traced window, with the scope map of
    the program's chunk, kept beside the trace as ``layers.json.gz``."""
    import time
    path = raw_trace()
    if path is None:
        raise FileNotFoundError("no raw trace beside a reduced.json.gz")
    t0 = time.perf_counter()
    form = load(path, ctx["device"])
    if any(abs(a - b) > 1e-9 for a, b in zip(form["window"], ctx["window"])):
        raise ValueError(f"{path} holds window {form['window']}, the run "
                         f"{list(ctx['window'])}")
    form["updates"] = ctx["updates"]
    try:
        from repro.scopes import LAYER_SCOPES
    except ImportError:
        LAYER_SCOPES = ()
    form["layers"] = list(LAYER_SCOPES)
    t1 = time.perf_counter()
    text = chunk_text(ctx["config"], ctx["traffic"]) if LAYER_SCOPES else ""
    smap = scope_map(text)
    seen = {n for n, _, _ in form["ops"]}
    form["scopes"] = {n: v for n, v in smap.items() if n in seen}
    form["notes"] = {"load_s": t1 - t0,
                     "scope_map_s": time.perf_counter() - t1,
                     "instructions": len(smap),
                     "with_op_name": sum(1 for v in smap.values() if v[1])}
    # <out>/traces/<run>/plugins/profile/<time>/<host>.xplane.pb
    run_dir = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(path))))
    trace.save(form, os.path.join(run_dir, "layers.json.gz"))
    return form
