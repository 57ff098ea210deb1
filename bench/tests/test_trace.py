"""The reduction from trace intervals to metrics, on a hand-made trace and
on one recorded on a TPU v5e."""
import glob
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def handmade():
    return {"devices": {"0": {
        "ops": [["fusion.1", 1.0, 0.5], ["grouped_bmm.3", 1.25, 0.5],
                ["flash_fwd.2", 2.0, 1.0], ["fusion.1", 4.0, 0.25]],
        "modules": [["jit_step", 1.0, 3.25]]}},
        "host": [["bench.window", 0.5, 4.0], ["bench.step", 0.5, 1.0],
                 ["bench.step", 1.6, 0.3]]}


def test_union_merges_and_clips(handmade):
    ops = handmade["devices"]["0"]["ops"]
    assert trace.union(ops, 0.0, 10.0) == [(1.0, 1.75), (2.0, 3.0), (4.0, 4.25)]
    assert trace.union(ops, 1.5, 4.1) == [(1.5, 1.75), (2.0, 3.0), (4.0, 4.1)]


def test_op_names_from_hlo_text():
    text = "%fusion.31 = bf16[39321600]{0:T(1024)} fusion(bf16[16384,6144]{1,0} %b)"
    assert trace.op_name(text) == "fusion.31"
    assert trace.op_label(text) == "fusion.31 = bf16[39321600]"


def test_busy_and_idle(handmade):
    lo, hi = trace.window(handmade)
    assert (lo, hi) == (0.5, 4.5)
    assert trace.busy_s(handmade, "0", lo, hi) == pytest.approx(2.0)
    gaps = trace.idle_gaps(handmade, "0", lo, hi)
    assert gaps == [(0.5, 1.0), (1.75, 2.0), (3.0, 4.0), (4.25, 4.5)]


def test_breakdown_names_gaps_by_host_span(handmade):
    b = trace.breakdown(handmade, "0", 0.5, 4.5)
    assert b["device_ops"][0] == ["flash_fwd.2", 1.0]
    assert dict(b["device_ops"])["fusion.1"] == pytest.approx(0.75)
    assert b["idle_gaps"][0] == ["outside any benchmark span", 1.0]
    assert b["idle_gaps"][1][0] == "bench.step"


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace(path):
    reduced = trace.read_saved(path)
    lo, hi = trace.window(reduced)
    assert hi > lo
    dev = sorted(reduced["devices"], key=int)[0]
    busy = trace.busy_s(reduced, dev, lo, hi)
    assert 0 < busy <= hi - lo
    b = trace.breakdown(reduced, dev, lo, hi)
    assert len(b["device_ops"]) == 10


MARL_METRICS = ["device_idle.marl", "step_mfu.marl"]


@pytest.mark.parametrize("metric", MARL_METRICS)
def test_marl_readers_on_recorded_trace(metric):
    """Each reader of the IC3Net cell finds what it reads in a trace of
    that cell recorded on a TPU v5e (2 dispatches of 10 updates), and reads
    a share in (0, 100]."""
    import importlib.util
    import json
    from bench import flops, peaks
    reduced = trace.read_saved(os.path.join(HERE, "data", "ic3net-dense.pp-b1024.json.gz"))
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "configs", "ic3net-dense.json")) as f:
        c = json.load(f)
    ctx = {"trace": reduced, "device": "0", "window": trace.window(reduced),
           "updates": 20, "chips": 1, "peaks": peaks.peaks("TPU v5 lite"),
           "work": {"update_ops": flops.ic3net_update(c, 1024)}}
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(root, "metrics", f"{metric}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(ctx)
    assert value is not None and 0 < value <= 100
