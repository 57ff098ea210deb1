"""Device time of the LM training step by layer, from the raw profiler
trace of a traced window, and the readings built on it.

The step names its layers with ``jax.named_scope``
(``repro.scopes.LM_SCOPES``); the compiled step's HLO text, which the
runner keeps in ``ctx["work"]["hlo"]``, gives each instruction its
``op_name`` (``bench.scopes.scope_map``). An operation belongs to the
innermost layer on its path (``bench.scopes.layer_of``), the flash
kernels (``flash_fwd``, ``flash_dq``, ``flash_dkdv``) to the attention
core inside ``mla``. XLA writes the grouped products of
``jax.lax.ragged_dot`` as kernels of their own named ``ragged-dot-*``
whose metadata drops the scope; they are the held experts' products and
count to ``experts``. Device and host clocks are aligned as for the MARL
step (``bench.scopes.align``).
"""
from __future__ import annotations

import json

from bench import harness, lmwork, scopes, trace

FLASH = ("flash_fwd", "flash_dq", "flash_dkdv")
MOE = ("moe_route", "experts", "shared_experts")


def layer(instruction: str, op_name: str, layers) -> str | None:
    if instruction.startswith("ragged-dot"):
        return "experts"
    return scopes.layer_of(op_name, layers)


def analyse(form: dict) -> dict:
    """Seconds of device time by layer over the step's runs in the window,
    and the busy time of those runs, from the plain form with its scope
    map."""
    offset, _ = scopes.align(form)
    lo, hi = form["window"]
    spans: dict[str, float] = {}
    for name, s, d, _ in form["modules"]:
        s += offset
        spans[scopes.module_base(name)] = (
            spans.get(scopes.module_base(name), 0.0)
            + max(0.0, min(s + d, hi) - max(s, lo)))
    step = max(spans, key=spans.get)
    runs = [(s + offset, s + d + offset) for name, s, d, _ in form["modules"]
            if scopes.module_base(name) == step and s + offset < hi
            and s + d + offset > lo]
    layers = list(form["layers"]) + list(FLASH)
    by_layer: dict = {}
    leaves, mapped, k = [], 0.0, 0
    for n, s, d in form["ops"]:
        s += offset
        mid = s + d / 2
        while k < len(runs) and runs[k][1] < mid:
            k += 1
        if k == len(runs):
            break
        if mid < runs[k][0] or not lo <= mid <= hi:
            continue
        opcode, op_name = form["scopes"].get(n, (None, ""))
        if opcode in scopes.CONTAINERS:
            continue
        leaves.append((n, s, d))
        mapped += d if opcode is not None else 0.0
        key = layer(n, op_name, layers)
        by_layer[key] = by_layer.get(key, 0.0) + d
    leaf_s = sum(by_layer.values())
    return {"by_layer": by_layer, "steps": len(runs), "step": step,
            "busy_s": trace.covered(trace.union(leaves, lo, hi)),
            "offset_ms": 1e3 * offset,
            "mapped_share": mapped / leaf_s if leaf_s else 0.0}


_CACHE: dict = {}


def metrics(ctx: dict) -> dict:
    """The LM step's per-layer metrics of the traced window in ``ctx``,
    computed once a window; {} where they cannot be read, with the reason
    in the log. A program without ``repro.scopes.LM_SCOPES`` gives none."""
    key = tuple(ctx["window"])
    if key not in _CACHE:
        try:
            _CACHE[key] = _metrics(ctx)
        except Exception as e:  # noqa: BLE001 — a reader never raises
            harness.log(f"LM layer metrics not read: {type(e).__name__}: {e}")
            _CACHE[key] = {}
    return _CACHE[key]


def _metrics(ctx: dict) -> dict:
    from repro.scopes import LM_SCOPES
    work = ctx["work"]
    path = scopes.raw_trace()
    if path is None:
        raise FileNotFoundError("no raw trace beside a reduced.json.gz")
    form = scopes.load(path, ctx["device"])
    form["layers"] = list(LM_SCOPES)
    seen = {n for n, _, _ in form["ops"]}
    form["scopes"] = {n: v for n, v in scopes.scope_map(work["hlo"]).items()
                      if n in seen}
    a = analyse(form)
    steps, t = a["steps"], a["by_layer"]
    if steps != ctx["updates"]:
        raise ValueError(f"{steps} step runs in the window, "
                         f"{ctx['updates']} dispatched")
    ms = {name: 1e3 * sum(t.get(x, 0.0) for x in group) / steps
          for name, group in (("mla_ms.lm", ("mla",) + FLASH),
                              ("moe_ms.lm", MOE))}
    ms["rest_ms.lm"] = 1e3 * a["busy_s"] / steps - sum(ms.values())
    peak = ctx["peaks"]["bf16_flops_per_s"]
    routed = work["routed"][-steps:]
    experts = sum(lmwork.routed_work(work, r) for r in routed)
    lo, hi = ctx["window"]
    out = dict(ms)
    out["step_mfu.lm"] = 100.0 * (steps * work["step"] + experts) / (
        (hi - lo) * ctx["chips"] * peak)
    t_exp = t.get("experts", 0.0)
    t_core = sum(t.get(x, 0.0) for x in FLASH)
    if t_exp:
        out["experts_roofline.lm"] = 100.0 * experts / (t_exp * peak)
    if t_core:
        out["attn_core_roofline.lm"] = (100.0 * steps * work["attn_core"]
                                        / (t_core * peak))
    detail = {"step": a["step"], "steps": steps, "offset_ms": a["offset_ms"],
              "mapped_share": a["mapped_share"],
              "busy_ms_per_step": 1e3 * a["busy_s"] / steps,
              "ms_per_step_by_scope": {str(k): 1e3 * v / steps
                                       for k, v in sorted(
                                           t.items(), key=lambda kv: -kv[1])}}
    harness.log("lm layers", json.dumps(dict(out, **detail)))
    return out
