"""The MARL step's layer metrics (bench/scopes.py), on a hand-made trace
and on one recorded on a TPU v5e."""
import importlib.util
import os

import pytest

from bench import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "layers", "ic3net-dense.pp-b1024.json.gz")
LAYERS = ["rollout", "env", "policy", "comm", "encoder", "lstm", "heads",
          "sample", "a2c", "rmsprop", "plan_refresh"]
METRICS = scopes.DEVICE_METRICS + ("unscoped_ms.marl", "idle_fetch_ms.marl",
                                   "idle_dispatch_ms.marl")
BODY = "jit(_scan_chunk)/while/body/closed_call/"
FWD = BODY + "jvp(vmap(rollout))/while/body/closed_call/"
BWD = BODY + "transpose(jvp(vmap(rollout)))/while/body/closed_call/"


@pytest.fixture
def handmade():
    """Two dispatches, each a convert program and a chunk of 2 updates; the
    device clock runs 1 ms behind the host's. Each chunk: env 1 ms, policy
    forward 2, sample 1, policy backward 3, the rollout's saves 1, loss and
    RMSprop 0.5 each, an unnamed copy 0.5, inside a 10 ms ``while``."""
    chunk_ops = [("while.1", 0.0, 0.010), ("fusion.env", 0.0, 0.001),
                 ("dot.fwd", 0.001, 0.002), ("fusion.sample", 0.003, 0.001),
                 ("dot.bwd", 0.004, 0.003), ("fusion.save", 0.007, 0.001),
                 ("fusion.loss", 0.008, 0.0005), ("fusion.opt", 0.0085, 0.0005),
                 ("copy.1", 0.009, 0.0005)]
    ops = []
    for t0 in (0.0005, 0.0145):
        ops.append(["convert.1", t0, 0.0001])
        ops.extend([n, t0 + 0.0015 + s, d] for n, s, d in chunk_ops)
    return {
        "window": [0.0, 0.030], "updates": 4, "layers": LAYERS,
        "modules": [["jit_convert_element_type(1)", 0.0005, 0.0001, 1],
                    ["jit__scan_chunk(2)", 0.002, 0.010, 2],
                    ["jit_convert_element_type(1)", 0.0145, 0.0001, 3],
                    ["jit__scan_chunk(2)", 0.016, 0.010, 4]],
        "ops": ops,
        "host": {
            "launch": [[0.0015, 0.0001], [0.0025, 0.0001],
                       [0.0150, 0.0001], [0.0165, 0.0001]],
            "done": [[0.0020, 0.0001], [0.0135, 0.0001],
                     [0.0160, 0.0001], [0.0275, 0.0001]],
            "fetch": [[0.0028, 0.0107], [0.0136, 0.0004],
                      [0.0163, 0.0109], [0.0273, 0.0004]],
            "dispatch": [["PjitFunction(convert_element_type)", 0.0010, 0.0006],
                         ["PjitFunction(convert_element_type)", 0.0010, 0.0006],
                         ["PjitFunction(_scan_chunk)", 0.0018, 0.0009],
                         ["PjitFunction(convert_element_type)", 0.0145, 0.0006],
                         ["PjitFunction(_scan_chunk)", 0.0153, 0.0009]]},
        "scopes": {
            "convert.1": ["convert", "jit(convert_element_type)/convert"],
            "while.1": ["while", "jit(_scan_chunk)/while"],
            "fusion.env": ["fusion", FWD + "env/add"],
            "dot.fwd": ["dot", FWD + "policy/lstm/dot_general"],
            "fusion.sample": ["fusion", FWD + "sample/jit(log_softmax)/sub"],
            "dot.bwd": ["dot", BWD + "policy/lstm/dot_general"],
            "fusion.save": ["fusion", BODY + "jvp(vmap(rollout))/while/body/"
                            "dynamic_update_slice"],
            "fusion.loss": ["fusion", BODY + "jvp(a2c)/mul"],
            "fusion.opt": ["fusion", BODY + "rmsprop/sqrt"],
            "copy.1": ["copy", ""]}}


def test_layer_of_is_the_innermost_scope_through_transforms():
    assert scopes.layer_of(BWD + "policy/lstm/dot_general", LAYERS) == "lstm"
    assert scopes.layer_of(BODY + "jvp(a2c)/mul", LAYERS) == "a2c"
    assert scopes.layer_of(FWD[:-len("while/body/closed_call/")]
                           + "while/body/dynamic_update_slice",
                           LAYERS) == "rollout"
    assert scopes.layer_of(BODY + "add", LAYERS) is None
    # a scope is a whole component, not a prefix of one
    assert scopes.layer_of(BODY + "policy_step/add", LAYERS) is None


@pytest.mark.parametrize("op_name,metric", [
    (FWD + "policy/heads/dot_general", "policy_fwd_ms.marl"),
    (BWD + "policy/heads/dot_general", "policy_bwd_ms.marl"),
    (BWD + "policy/add_any", "policy_bwd_ms.marl"),
    (FWD + "sample/exp", "sample_ms.marl"),
    (BWD + "sample/jit(log_softmax)/neg", "sample_ms.marl"),
    (FWD + "env/select_n", "env_ms.marl"),
    (BODY + "transpose(jvp(a2c))/mul", "loss_optim_ms.marl"),
    (BWD + "dynamic_slice", None),
    (BODY + "plan_refresh/cond", None),
])
def test_the_backward_is_what_lies_under_transpose(op_name, metric):
    layer = scopes.layer_of(op_name, LAYERS)
    assert scopes.bucket(layer, "transpose(" in op_name) == metric


def test_alignment_starts_no_program_before_its_launch(handmade):
    offset, bound = scopes.align(handmade)
    assert offset == pytest.approx(0.001)
    assert bound == pytest.approx(0.0014)
    for m, launch in zip(handmade["modules"], handmade["host"]["launch"]):
        assert m[1] + offset >= launch[0] - 1e-12


def test_alignment_refuses_unpaired_programs(handmade):
    handmade["host"]["launch"].pop()
    with pytest.raises(ValueError):
        scopes.align(handmade)


def test_layers_leaves_only_and_idle_by_host_activity(handmade):
    out = scopes.analyse(handmade)
    detail = out.pop("_detail")
    want = {"env_ms.marl": 0.5, "policy_fwd_ms.marl": 1.0,
            "policy_bwd_ms.marl": 1.5, "sample_ms.marl": 0.5,
            "loss_optim_ms.marl": 0.5,
            # the saves and the copy; the ``while`` around them is no leaf
            "unscoped_ms.marl": 0.75,
            "idle_fetch_ms.marl": 1.2, "idle_dispatch_ms.marl": 1.4}
    assert out == pytest.approx(want)
    assert detail["chunk"] == "jit__scan_chunk"
    assert detail["dispatches"] == 2
    assert detail["busy_ms_per_update"] == pytest.approx(4.75)
    assert detail["idle_ms_per_dispatch"] == pytest.approx(4.9)
    assert detail["idle_chunk_launch_ms_per_dispatch"] == pytest.approx(0.85)
    assert detail["mapped_share"] == pytest.approx(1.0)


def test_a_program_without_scopes_gives_the_idle_pair_alone(handmade):
    handmade["layers"] = []
    handmade["scopes"] = {}
    out = scopes.analyse(handmade)
    out.pop("_detail")
    assert set(out) == {"idle_fetch_ms.marl", "idle_dispatch_ms.marl"}


def test_instruction_map_from_hlo_text():
    text = "\n".join([
        "ENTRY %main.9 (p.1: f32[4]) -> f32[4] {",
        '  %fusion.31 = f32[20,1024]{1,0:T(8,128)} fusion(f32[4]{0} %p.1), '
        'kind=kLoop, calls=%fused.1, metadata={op_name="jit(f)/policy/lstm/'
        'mul" source_file="x.py" source_line=3}',
        "  %while.2 = (s32[], f32[4]{0:T(256)}) while((s32[], f32[4]) %t), "
        "condition=%c, body=%b",
        "  ROOT %copy.3 = f32[4]{0} copy(f32[4]{0} %fusion.31)",
        "}"])
    m = scopes.scope_map(text)
    assert m["fusion.31"] == ["fusion", "jit(f)/policy/lstm/mul"]
    assert m["while.2"] == ["while", ""]
    assert m["copy.3"] == ["copy", ""]


@pytest.fixture(scope="module")
def recorded():
    return trace.read_saved(RECORDED)


def test_recorded_layers_sum_to_the_busy_time(recorded):
    out = scopes.analyse(recorded)
    detail = out.pop("_detail")
    six = sum(out[m] for m in scopes.DEVICE_METRICS + ("unscoped_ms.marl",))
    assert six == pytest.approx(detail["busy_ms_per_update"], rel=0.01)
    assert all(out[m] > 0 for m in scopes.DEVICE_METRICS)
    assert out["unscoped_ms.marl"] >= 0
    assert detail["mapped_share"] >= 0.99


def test_recorded_idle_pair_fits_the_idle_time(recorded):
    out = scopes.analyse(recorded)
    detail = out.pop("_detail")
    assert out["idle_fetch_ms.marl"] > 0 and out["idle_dispatch_ms.marl"] > 0
    assert (out["idle_fetch_ms.marl"] + out["idle_dispatch_ms.marl"]
            <= detail["idle_ms_per_dispatch"])


def test_recorded_alignment(recorded):
    offset, bound = scopes.align(recorded)
    assert offset <= bound
    for m, launch in zip(recorded["modules"], recorded["host"]["launch"]):
        assert m[1] + offset >= launch[0] - 1e-12


@pytest.mark.parametrize("metric", METRICS)
def test_each_reader_reads_the_recorded_trace(metric, recorded, monkeypatch):
    monkeypatch.setattr(scopes, "form_of", lambda ctx: recorded)
    monkeypatch.setattr(scopes, "_CACHE", {})
    ctx = {"window": tuple(recorded["window"]), "updates": recorded["updates"],
           "traffic": {"batch": 1024}, "config": {"max_steps": 20}}
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(BENCH, "metrics", f"{metric}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(ctx)
    assert isinstance(value, float) and value > 0
