"""Main-path Pallas kernels compiled for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler installed with jaxlib compiles for a
topology that is described, not attached. This catches what interpret-mode
tests cannot — Mosaic's (8, 128) block-tiling rule, unsupported in-kernel
ops, VMEM overruns. Nothing runs, so results are checked elsewhere
(``tests/test_kernels.py`` on the CPU, ``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import base64
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.grouped as grouped_mod
from repro.core.flgw import FLGWConfig
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flgw_matmul import ops as fops
from repro.kernels.plan_encode import ops as pe_ops
from repro.kernels.tiling import compute_cap
from repro.marl import envs, ic3net
from repro.marl import train as mt
from repro.optim.optimizers import rmsprop_init

# IC3Net (paper config): hidden 128, 8 agents, env batch 32.
IC3_HIDDEN, IC3_AGENTS, IC3_BATCH = 128, 8, 32
# gemma2-2b: d_model 2304, d_ff 9216, 13 scanned blocks per pattern slot,
# 8 query / 4 kv heads of width 256, local window 4096, softcap 50.
D_MODEL, D_FF, N_BLOCKS = 2304, 9216, 13


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _plan_shapes(sharding, m, n, g, lead=(), dtype=jnp.bfloat16):
    """Shapes of one GroupPlan's layout leaves plus its compact weights,
    at the grouped path's default capacity slack."""
    slack = FLGWConfig().capacity_slack
    cap_m, cap_n = compute_cap(m, g, slack), compute_cap(n, g, slack)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(lead + shape, dt, sharding=sharding)
    return (s((g, cap_m), jnp.int32), s((g, cap_n), jnp.int32),
            s((g, cap_m), jnp.bool_), s((g, cap_n), jnp.bool_),
            s((g, cap_m, cap_n), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_bmm_ic3net_width(one_chip, dtype):
    """IC3Net's grouped projections: one (agents, hidden) slab per env,
    vmapped over the env batch as the rollout does."""
    g = 4
    rid, cid, rv, cv, _ = _plan_shapes(one_chip, IC3_HIDDEN, IC3_HIDDEN, g)
    x = jax.ShapeDtypeStruct((IC3_BATCH, IC3_AGENTS, IC3_HIDDEN), dtype,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((IC3_HIDDEN, IC3_HIDDEN), dtype,
                             sharding=one_chip)

    def f(x, w, rid, cid, rv, cv):
        return jax.vmap(lambda xe: fops.grouped_matmul(
            xe, w, rid, cid, rv, cv, interpret=False))(x)
    _compile_text(f, x, w, rid, cid, rv, cv)


@pytest.mark.parametrize("m,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_grouped_bmm_gemma2_mlp_width(one_chip, m, n):
    """gemma2-2b MLP up/gate (2304 -> 9216) and down (9216 -> 2304)
    projections at G=4, a 4-slot decode batch, bf16."""
    rid, cid, rv, cv, _ = _plan_shapes(one_chip, m, n, 4)
    x = jax.ShapeDtypeStruct((4, m), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((m, n), jnp.bfloat16, sharding=one_chip)
    _compile_text(lambda *a: fops.grouped_matmul(*a, interpret=False),
                  x, w, rid, cid, rv, cv)


def test_serving_consume_path_with_cached_weights(one_chip):
    """The serving consume path: cached compact weights (``GroupPlan.wc``)
    fed straight to the kernel, stacked over gemma2-2b's scanned blocks
    as the decode scan slices them."""
    rid, cid, rv, cv, wc = _plan_shapes(one_chip, D_MODEL, D_FF, 4,
                                        lead=(N_BLOCKS,))
    x = jax.ShapeDtypeStruct((4, D_MODEL), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((N_BLOCKS, D_MODEL, D_FF), jnp.bfloat16,
                             sharding=one_chip)

    def f(x, w, rid, cid, rv, cv, wc):
        def body(h, xs):
            y = fops.grouped_matmul(h, *xs, interpret=False)
            return y[:, :D_MODEL], None
        return jax.lax.scan(body, x, (w, rid, cid, rv, cv, wc))[0]
    _compile_text(f, x, w, rid, cid, rv, cv, wc)


@pytest.mark.parametrize("lead,m", [((), 512), ((N_BLOCKS,), D_FF)])
def test_assign_slots_rank_and_place(one_chip, lead, m):
    """Plan encode at one tile (M=512) and at gemma2-2b's d_ff (M=9216:
    18 rank tiles, stacked over the scanned blocks)."""
    scores = jax.ShapeDtypeStruct(lead + (m, 4), jnp.float32,
                                  sharding=one_chip)
    text = _compile_text(
        lambda s: pe_ops.balanced_assign(
            s, 1, FLGWConfig().capacity_slack, interpret=False), scores)
    assert text.count("tpu_custom_call") >= 2        # rank + place


def _flash_shapes(sharding):
    q = jax.ShapeDtypeStruct((1, 8, 4096, 256), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, 4, 4096, 256), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, kv


_FLASH_KW = dict(causal=True, window=1024, softcap=50.0, interpret=False)


def test_flash_fwd_gemma2_heads(one_chip):
    _compile_text(lambda q, k, v: flash_ops.flash_attention(
        q, k, v, **_FLASH_KW), *_flash_shapes(one_chip))


def test_flash_bwd_gemma2_heads(one_chip):
    def loss(q, k, v):
        out = flash_ops.flash_attention(q, k, v, **_FLASH_KW)
        return jnp.sum(out.astype(jnp.float32))
    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)),
                         *_flash_shapes(one_chip))
    assert text.count("tpu_custom_call") >= 3        # fwd, dq, dkv


def _rollout_chunk_text(sharding, groups: int, monkeypatch) -> str:
    """The MARL training chunk (two updates) at IC3Net's width, 3 agents,
    20 steps, env batch 32, compiled at the highest precision."""
    for mod in (grouped_mod, fops, pe_ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    env = envs.get("predator_prey")
    ecfg = env.config_cls(n_agents=3, size=5, vision=0, max_steps=20)
    cfg = ic3net.IC3NetConfig(hidden=IC3_HIDDEN, n_agents=3,
                              obs_dim=env.obs_dim(ecfg),
                              n_actions=env.n_actions(ecfg),
                              flgw_groups=groups,
                              flgw_path="grouped" if groups > 1 else "dense")
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: ic3net.init(k, cfg)[0], key)
    plans = jax.eval_shape(lambda p: ic3net.encode_plans(p, cfg), params)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (params, jax.eval_shape(rmsprop_init, params),
         jax.eval_shape(lambda: key), plans,
         jax.ShapeDtypeStruct((), jnp.int32)))
    # Whether a kernel interprets is fixed when it is traced: keep this
    # trace out of, and away from, the caches the CPU tests use.
    jax.clear_caches()
    try:
        with jax.default_matmul_precision("highest"):
            return mt._train_chunk.lower(*args, 2, cfg, ecfg,
                                         mt.TrainConfig(batch=IC3_BATCH), env,
                                         None).compile().as_text()
    finally:
        jax.clear_caches()


def _forward_rollout_widths(text: str) -> list:
    """Trailing widths of the per-step buffers the forward step scan stacks
    ([steps, batch, agents, width]) for its backward."""
    for line in text.splitlines():
        m = re.match(r"\s*%?\S+ = \((.*)\) while\(", line)
        if m and 'jvp(vmap(rollout))/while"' in line \
                and "transpose(" not in line:
            return [int(d.split(",")[-1]) for d in
                    re.findall(r"f32\[(20,%d,3,\d+)\]" % IC3_BATCH,
                               m.group(1))]
    raise AssertionError("no forward rollout scan in the chunk")


@pytest.mark.parametrize("groups", [1, 4], ids=["dense", "grouped-g4"])
def test_rollout_scan_stacks_few_residuals(one_chip, groups, monkeypatch):
    """The rematerialised policy step: the forward scan stacks at most 8
    H-wide buffers a step (a 4H buffer counts 4), and on the grouped path
    no kernel runs again inside the recomputation."""
    text = _rollout_chunk_text(one_chip, groups, monkeypatch)
    widths = _forward_rollout_widths(text)
    assert sum(w // IC3_HIDDEN for w in widths) <= 8, widths
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert bool(calls) == (groups > 1)
    assert not [l for l in calls if "rematted_computation" in l]


def test_deepseek_v2_lite_cut_train_step(one_chip, monkeypatch):
    """The benchmark's DeepSeek-V2-Lite cut at its published widths (6
    layers, 8 of 64 experts held, 12,800-id vocabulary), 2 sequences of
    4096: the whole train step, with MLA's flash kernels at a 192-wide
    query/key head beside a 128-wide value head and the held experts'
    dropless grouped products (two chunks of the dispatch)."""
    import json
    from bench.runners import lm
    from repro.train import state as state_lib
    from repro.train import step as step_lib
    monkeypatch.setattr(flash_ops, "default_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "deepseek-v2-lite-e8.json")) as f:
        cfg = lm.program_config(json.load(f))
    spec = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip), t)
    state = spec(state_lib.abstract_state(cfg))
    batch = {k: jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
             for k in ("tokens", "targets", "positions")}
    jax.clear_caches()
    try:
        text = jax.jit(step_lib.make_train_step(cfg), donate_argnums=0).lower(
            state, batch).compile().as_text()
    finally:
        jax.clear_caches()
    kernels = [l for l in text.splitlines() if "tpu_custom_call" in l]
    flash = [l for l in kernels if 'op_name="' in l and "/flash_" in l]
    assert any("bf16[2,16,4096,128]" in l for l in flash)     # value head
    assert any("bf16[2,16,4096,192]" in l for l in flash)     # q/k head
    # the held experts' products (forward, and both gradients): each
    # kernel's grid over rows is sized at run time, by the tiles that the
    # held assignments fill, so their work follows the routed count
    products = [l for l in kernels if re.search(r"%ragged-dot-(?!metadata)", l)]
    assert len(products) >= 3
    for line in products:
        body = base64.b64decode(re.search(r'"body":"([^"]+)"', line)[1])
        bounds = re.search(rb"iteration_bounds = array<i64: ([^>]*)>", body)[1]
        assert b"-9223372036854775808" in bounds, bounds     # dynamic
