"""Profiler trace capture and its reduction to intervals.

A traced window is recorded with ``jax.profiler`` into a directory under
``.bench_out/``. ``load`` reduces the ``.xplane.pb`` to plain intervals:
for each device, the operations (``XLA Ops`` line) and the programs
(``XLA Modules`` line); for the host, the benchmark's own spans (names
starting with ``bench.``). Times are seconds on the profiler's clock.
Everything after ``load`` works on that plain form, so it can be checked
on a recorded trace without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@contextlib.contextmanager
def capture(directory: str):
    import jax
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str) -> dict:
    """Reduce the newest trace under ``directory`` to plain intervals."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    data = ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                dev = out["devices"].setdefault(m.group(1),
                                                {"ops": [], "modules": []})
                dev[key].extend((e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events)
            elif not m and plane.name.startswith("/host"):
                out["host"].extend((e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                                   for e in line.events
                                   if e.name.startswith("bench."))
    for dev in out["devices"].values():
        for key in dev:
            dev[key].sort(key=lambda e: e[1])
    out["host"].sort(key=lambda e: e[1])
    return out


def save(reduced: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(reduced, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --- reductions ----------------------------------------------------------

def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged (start, end) intervals of (name, start, dur) events, clipped
    to [lo, hi]."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals
                   if s + d > lo and s < hi)
    merged: list[list[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(spans) -> float:
    return sum(e - s for s, e in spans)


def window(reduced: dict, span: str = "bench.window") -> tuple[float, float]:
    """[start, end] of the host span that marks the traced window."""
    for name, s, d in reduced["host"]:
        if name == span:
            return s, s + d
    raise KeyError(f"no host span {span!r} in the trace")


def busy_s(reduced: dict, device: str, lo: float, hi: float) -> float:
    return covered(union(reduced["devices"][device]["ops"], lo, hi))


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device operation: the trace names an
    operation by its HLO text, ``%fusion.31 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_label(event_name: str) -> str:
    """Instruction name and result type, without layouts."""
    return event_name.lstrip("%").split("{", 1)[0].strip()


def idle_gaps(reduced: dict, device: str, lo: float, hi: float):
    """(start, end) of each stretch of [lo, hi] with no device operation."""
    gaps, t = [], lo
    for s, e in union(reduced["devices"][device]["ops"], lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def host_label(reduced: dict, t: float, window_span: str = "bench.window"):
    """Innermost benchmark span on the host at time ``t``."""
    best = None
    for name, s, d in reduced["host"]:
        if name != window_span and s <= t <= s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "outside any benchmark span"


def top_ops(reduced: dict, device: str, lo: float, hi: float, n: int = 10):
    """The ``n`` HLO instructions that took most device time in [lo, hi],
    each labelled with its result type, with their seconds."""
    total: dict[str, float] = {}
    for name, s, d in reduced["devices"][device]["ops"]:
        if s + d > lo and s < hi:
            label = op_label(name)
            total[label] = total.get(label, 0.0) + min(s + d, hi) - max(s, lo)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def breakdown(reduced: dict, device: str, lo: float, hi: float) -> dict:
    """Top device operations and the longest idle gaps, each gap named by
    the host span that was open at its middle."""
    gaps = sorted(idle_gaps(reduced, device, lo, hi), key=lambda g: g[0] - g[1])
    return {"device_ops": [[k, v] for k, v in top_ops(reduced, device, lo, hi)],
            "idle_gaps": [[host_label(reduced, (s + e) / 2), e - s]
                          for s, e in gaps[:10]]}


# --- shares the per-layer readers report (percent) -----------------------

def idle_pct(ctx: dict) -> float:
    """Share of the traced window in which the device ran no operation."""
    lo, hi = ctx["window"]
    return 100.0 * (1.0 - busy_s(ctx["trace"], ctx["device"], lo, hi)
                    / (hi - lo))


def mfu_pct(ctx: dict) -> float:
    """Operations the traced updates need over the window's length, the
    chips and the bf16 peak."""
    lo, hi = ctx["window"]
    ops = ctx["updates"] * ctx["work"]["update_ops"]
    return 100.0 * ops / ((hi - lo) * ctx["chips"]
                          * ctx["peaks"]["bf16_flops_per_s"])

