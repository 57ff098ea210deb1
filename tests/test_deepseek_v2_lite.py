"""DeepSeek-V2-Lite's smoke configuration against the benchmark's plain
float32 reference (``bench/reference/deepseek_v2.py``), on the CPU.

The program runs in float32 here so that the two differ by summation
order alone; on the chip the cell compares the bfloat16 program with the
same reference. Tolerances: 2e-5 relative on logits and losses (float32
sums of a few hundred products in different orders), 1e-4 relative on
gradients (the backward adds a few thousand such terms per entry).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v2 as ref
from bench.runners import lm as lm_runner
from repro.configs import registry
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention import ref as flash_ref
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.models.layers import softcap
from repro.train import state as state_lib
from repro.train import step as step_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, S = 2, 64


def _file():
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite-e8.json")) as f:
        return json.load(f)


def small(**kw) -> dict:
    """The benchmark's configuration file at smoke widths, every expert
    held; YaRN's original context cut to 16 positions so that the ramp
    between its two frequency regimes falls inside the rope columns used."""
    c = _file()
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, moe_intermediate_size=32,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, vocab_size=256, num_hidden_layers=3,
             n_routed_experts=8, router_experts=8, num_experts_per_tok=2)
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    c.update(kw)
    return c


def program(c, use_flash=False):
    return lm_runner.program_config(c).with_updates(dtype=jnp.float32,
                                                    use_flash=use_flash)


def _init(cfg, seed=0):
    return transformer.lm_init(jax.random.PRNGKey(seed), cfg)[0]


def _batch(vocab, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0, vocab)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:],
            "positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                          (B, S))}


def _close(a, b, rtol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rtol * scale, (what, np.abs(a - b).max(),
                                                 scale)


def test_logits_match_reference():
    c = small()
    cfg = program(c)
    params = _init(cfg)
    batch = _batch(c["vocab_size"])
    got, aux, _ = transformer.lm_apply(params, cfg, batch["tokens"],
                                       batch["positions"])
    mm = ref.product("highest")
    for b in range(B):
        want, bal = ref.logits(params, batch["tokens"][b], c, mm)
        _close(got[b], want, 2e-5, "logits")
    assert aux["routed"].shape == (c["num_hidden_layers"] - 1,)


def _ref_loss(params, batch, c):
    mm = ref.product("highest")
    total = 0.0
    for b in range(B):
        nll, bal = ref.sequence_loss(params, batch["tokens"][b],
                                     batch["targets"][b], c, mm)
        total = total + nll / (B * S) + c["aux_loss_alpha"] * bal / B
    return total


@pytest.mark.parametrize("flash", [False, True], ids=["plain", "flash"])
def test_loss_and_gradients_match_reference(flash, monkeypatch):
    """The train step's loss and every leaf of its gradient; the dropless
    dispatch in chunks of 32 tokens (four a step) so that its rematerialised
    chunk loop is the one checked."""
    monkeypatch.setattr(moe_mod, "DROPLESS_CHUNK", 32)
    c = small()
    cfg = program(c, use_flash=flash)
    params = _init(cfg)
    batch = _batch(c["vocab_size"])
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: step_lib._loss_fn(p, batch, cfg, q_chunk=S, banded=False),
        has_aux=True)(params)
    want, want_g = jax.value_and_grad(_ref_loss)(params, batch, c)
    _close(loss, want, 2e-5, "loss")
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        _close(g, w, 1e-4, jax.tree_util.keystr(path))
    # every assignment of the 2 x 64 tokens lands on a held expert
    np.testing.assert_array_equal(metrics["moe_routed"],
                                  [B * S * c["num_experts_per_tok"]] * 2)


def test_train_step_matches_reference_adamw():
    """One step of ``make_train_step`` (AdamW, clip 1.0) against the
    reference's first step from the same float32 weights."""
    c = small()
    cfg = program(c)
    state = state_lib.init_state(jax.random.PRNGKey(0), cfg)
    batch = _batch(c["vocab_size"])
    step = jax.jit(step_lib.make_train_step(cfg, lr=c["lr"], clip=c["clip"]))
    new, metrics = step(state, batch)
    losses, _, params = ref.train(state.params,
                                  [(batch["tokens"], batch["targets"])], c)
    _close(metrics["loss"], losses[0], 2e-5, "loss")
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(new.params)[0],
            jax.tree.leaves(params)):
        # AdamW's first step moves each weight by about lr; a gradient
        # entry that float32 rounding flips in sign moves it by 2 lr
        moved = np.abs(np.asarray(a) - np.asarray(b)) > 0.5 * c["lr"]
        assert moved.mean() < 1e-3, jax.tree_util.keystr(path)


def test_reference_init_fills_the_program_tree():
    """The reference makes the starting weights by the file's ``assumed``
    init, in the program's tree, and the runner rounds each leaf to the
    program's dtype: a share of the experts held, bfloat16 weights."""
    c = small(n_routed_experts=4, hidden_size=128)
    key = jax.random.PRNGKey(7)
    made = jax.jit(lambda k: ref.init_params(k, c))(key)
    got = lm_runner.initial_weights(c, key)
    want = state_lib.abstract_state(lm_runner.program_config(c)).params
    assert jax.tree.structure(made) == jax.tree.structure(want)
    for (path, a), g, w in zip(jax.tree_util.tree_flatten_with_path(made)[0],
                               jax.tree.leaves(got), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert a.shape == g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(a.astype(w.dtype)),
                                      np.asarray(g), err_msg=name)
        a = np.asarray(a)
        if "scale" in name:
            np.testing.assert_array_equal(a, 1.0, err_msg=name)
        else:       # the sample's std within 10% of the assumed one
            fan_in = a.shape[-1] if "head" in name else a.shape[-2]
            std = 0.02 if "embed" in name else fan_in ** -0.5
            assert abs(a.std() / std - 1) < 0.1, (name, a.std(), std)


@pytest.mark.parametrize("setting", [
    {"routed_scaling_factor": 2.5}, {"rope_scaling.mscale": 1.0},
    {"rope_scaling.beta_fast": 16}, {"rope_scaling.beta_slow": 2}])
def test_program_config_rejects_what_the_program_holds_fixed(setting):
    """Settings the program keeps as constants (DeepSeek-V2's values):
    a file that states another value is refused, not run as another
    model."""
    c = small()
    (name, value), = setting.items()
    if name.startswith("rope_scaling."):
        c["rope_scaling"] = dict(c["rope_scaling"], **{name[13:]: value})
    else:
        c[name] = value
    with pytest.raises(ValueError, match=name):
        lm_runner.program_config(c)


def test_mla_mixer_matches_reference():
    c = small()
    cfg = program(c)
    p = mla_mod.mla_init(jax.random.PRNGKey(3), cfg)[0]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, c["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    got, _ = mla_mod.mla(p, x, pos, cfg)
    mm = ref.product("highest")
    for b in range(B):
        _close(got[b], ref.mla(p, x[b], c, mm), 2e-5, "mla")


def test_yarn_frequencies_and_scale_as_published():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4096, beta 32 and 1,
    mscale 0.707): the formula written out."""
    cfg = registry.get_config("deepseek_v2_lite")
    dim, base = 64, 10000.0
    i = np.arange(0, dim, 2) / dim
    fast = base ** -i

    def corr(rot):
        return dim * np.log(4096 / (rot * 2 * np.pi)) / (2 * np.log(base))
    low, high = max(np.floor(corr(32)), 0), min(np.ceil(corr(1)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = fast * (1 - ramp) + fast / 40 * ramp
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(mla_mod.yarn_inv_freq(cfg), want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(_file()), want, rtol=1e-6)
    scale = 192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2
    assert mla_mod.softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert ref.softmax_scale(_file()) == pytest.approx(scale, rel=1e-12)


def test_flash_narrow_value_head_matches_ref():
    """q and k 192 wide, v 128 (MLA's heads), forward and backward, in
    interpret mode: a float32 kernel against the plain softmax, to the
    rounding of sums over 256 keys."""
    key = jax.random.PRNGKey(5)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, 2, 256, 192))
    k = jax.random.normal(kk, (1, 2, 256, 192))
    v = jax.random.normal(kv, (1, 2, 256, 128))
    go = jax.random.normal(kg, (1, 2, 256, 128))
    scale = 0.1

    def f_flash(q, k, v):
        return jnp.sum(flash_ops.flash_attention(
            q, k, v, True, 0, 0.0, scale, 128, 128, True) * go)

    def f_ref(q, k, v):
        return jnp.sum(flash_ref.ref_attention(q, k, v, causal=True,
                                               scale=scale) * go)
    out = flash_ops.flash_attention(q, k, v, True, 0, 0.0, scale, 128, 128,
                                    True)
    assert out.shape == (1, 2, 256, 128)
    np.testing.assert_allclose(
        out, flash_ref.ref_attention(q, k, v, causal=True, scale=scale),
        rtol=1e-5, atol=2e-5)
    for a, b in zip(jax.grad(f_flash, (0, 1, 2))(q, k, v),
                    jax.grad(f_ref, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _moe_setup(**kw):
    cfg = registry.get_smoke_config("deepseek_v2_lite").with_updates(
        dtype=jnp.float32, **kw)
    p = moe_mod.moe_init(jax.random.PRNGKey(6), cfg)[0]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.d_model))
    return cfg, p, x


def test_expert_shares_add_up_to_the_whole_layer():
    """8 experts, top 2: the shares {0-3} and {4-7}, with the shared
    experts (which every chip computes alike) counted once, give the
    uncut layer; each share's counter counts its own assignments."""
    cfg, p, x = _moe_setup(n_experts=8, top_k=2)
    whole, _, st = moe_mod.moe(p, x, cfg)
    shared = moe_mod.mlp(p["shared"], x, act=cfg.mlp_act)
    parts, routed = [], 0.0
    for first in (0, 4):
        share = dict(p, **{n: {"w": p[n]["w"][first:first + 4]}
                           for n in ("up", "gate", "down")})
        c = cfg.with_updates(experts_held=4, expert_first=first)
        out, _, s = moe_mod.moe(share, x, c)
        parts.append(out)
        routed += float(s["routed"])
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole,
                               rtol=1e-5, atol=1e-5)
    assert routed == float(st["routed"]) == x.shape[0] * x.shape[1] * 2


def test_dropless_keeps_every_assignment_under_skew():
    """A router biased to two experts: about seven in eight assignments go
    to experts 0 and 1, far past any capacity of t*k/E. Every assignment is
    computed: the layer equals the sum over each token's top-k written out
    expert by expert."""
    cfg, p, x = _moe_setup(n_experts=8, top_k=2)
    p = dict(p, router=p["router"].at[:, :2].add(3.0 * jnp.sign(
        jnp.mean(x, axis=(0, 1)))[:, None]))
    out, _, st = moe_mod.moe(p, x, cfg)
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    w, e = jax.lax.top_k(probs, 2)
    want = moe_mod.mlp(p["shared"], xf, act=cfg.mlp_act)
    for j in range(2):
        for ex in range(8):
            pe = {n: {"w": p[n]["w"][ex]} for n in ("up", "gate", "down")}
            y = moe_mod.mlp(pe, xf, act=cfg.mlp_act)
            want = want + jnp.where((e[:, j] == ex)[:, None],
                                    w[:, j:j + 1] * y, 0.0)
    np.testing.assert_allclose(out.reshape(-1, cfg.d_model), want,
                               rtol=1e-5, atol=1e-5)
    top2 = float(jnp.mean(jnp.isin(e, jnp.array([0, 1]))))
    assert top2 > 0.75 and float(st["peak"]) > 2.5
    assert float(st["routed"]) == xf.shape[0] * 2


def _past_groups_nan(lhs, rhs, group_sizes, real=jax.lax.ragged_dot):
    """``ragged_dot`` as the TPU's kernels leave it: rows past the groups
    are not written, here filled with NaN, in the output and in the
    gradient of ``lhs``."""
    def tail(a):
        rows = jnp.arange(a.shape[0])[:, None] < jnp.sum(group_sizes)
        return jnp.where(rows, a, jnp.nan)

    @jax.custom_vjp
    def f(lhs, rhs):
        return tail(real(lhs, rhs, group_sizes))

    def bwd(res, g):
        dl, dr = jax.vjp(lambda a, b: real(a, b, group_sizes), *res)[1](g)
        return tail(dl), dr
    f.defvjp(lambda lhs, rhs: (f(lhs, rhs), (lhs, rhs)), bwd)
    return f(lhs, rhs)


def test_held_share_ignores_rows_past_the_groups(monkeypatch):
    """With 4 of 8 experts held, about half the sorted assignments lie past
    the groups; whatever ``ragged_dot`` leaves there reaches neither the
    layer's output nor any gradient."""
    cfg, p, x = _moe_setup(n_experts=8, top_k=2)
    cfg = cfg.with_updates(experts_held=4)
    p = dict(p, **{n: {"w": p[n]["w"][:4]} for n in ("up", "gate", "down")})

    def loss(p, x):
        out, aux, _ = moe_mod.moe(p, x, cfg)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux
    want = jax.grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", _past_groups_nan)
    got = jax.grad(loss, (0, 1))(p, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(
            path))


def test_tied_head_is_the_embedding_to_the_bit():
    """A tied configuration keeps its parameter tree and reads the
    embedding as its head; an untied one with the embedding copied into
    its head gives the same logits to the bit."""
    cfg = registry.get_smoke_config("gemma2_2b")
    params = _init(cfg)
    assert "head" not in params
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, cfg.vocab)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    tied, _, _ = transformer.lm_apply(params, cfg, tokens, pos)
    hidden, _, _ = transformer.lm_apply(params, cfg, tokens, pos,
                                        return_hidden=True)
    np.testing.assert_array_equal(tied, softcap(
        (hidden @ params["embed"]["embedding"].T).astype(jnp.float32),
        cfg.logit_softcap))
    untied_cfg = cfg.with_updates(tie_embeddings=False)
    untied = dict(params, head={"w": params["embed"]["embedding"]})
    got, _, _ = transformer.lm_apply(untied, untied_cfg, tokens, pos)
    np.testing.assert_array_equal(got, tied)
    assert set(_init(untied_cfg)) == set(params) | {"head"}
