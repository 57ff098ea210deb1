"""What one rematerialised policy step keeps for its backward.

``ic3net.policy_step`` wraps the step in ``jax.checkpoint``: the backward
keeps the LSTM input and gate pre-activations (and the encoder's
pre-activation where a kernel computes it) and recomputes the rest. The
step scan stacks every kept residual over its steps, so their width is the
rollout's memory traffic. The gradients are those of the plain step.
"""
import jax
import jax.numpy as jnp
import pytest
from jax._src.ad_checkpoint import saved_residuals

from repro.marl import envs, ic3net
from repro.marl import train as mt

CASES = pytest.mark.parametrize("groups,path", [(1, "dense"), (4, "grouped")],
                                ids=["dense", "grouped-g4"])


def _setup(groups, path, hidden, max_steps):
    env = envs.get("predator_prey")
    ecfg = env.config_cls(n_agents=3, size=5, vision=0, max_steps=max_steps)
    cfg = ic3net.IC3NetConfig(hidden=hidden, n_agents=3,
                              obs_dim=env.obs_dim(ecfg),
                              n_actions=env.n_actions(ecfg),
                              flgw_groups=groups, flgw_path=path)
    params, _ = ic3net.init(jax.random.PRNGKey(0), cfg)
    return env, ecfg, cfg, params, ic3net.encode_plans(params, cfg)


@CASES
def test_policy_step_keeps_at_most_8h_per_agent(groups, path):
    b, h = 6, 32
    _, _, cfg, params, plans = _setup(groups, path, h, 3)
    a = cfg.n_agents
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    obs = jax.random.uniform(ks[0], (b, a, cfg.obs_dim))
    hid = jax.random.normal(ks[1], (b, a, h)) * 0.1
    cell = jax.random.normal(ks[2], (b, a, h)) * 0.1
    gate = jnp.ones((b, a))
    action = jnp.zeros((a,), jnp.int32)

    def step(params, hid, cell):
        def one(o, hh, cc, g):
            logits, value, gate_logits, hc = ic3net.policy_step(
                params, cfg, o, (hh, cc), g, plans)
            return mt._policy_terms(logits, gate_logits, action, g), value, hc
        return jax.vmap(one)(obs, hid, cell, gate)

    res = saved_residuals(step, params, hid, cell)
    # Per-agent residuals wider than the action logits: the step's own h and
    # c, and whatever the policy keeps. The observation is the env's data,
    # an input of the step, not counted.
    wide = [(s.shape[-1], why) for s, why in res
            if s.shape[:2] == (b, a) and s.ndim == 3
            and s.shape[-1] > cfg.n_actions and s.shape[-1] != cfg.obs_dim]
    width = sum(w for w, _ in wide)
    assert width <= 8 * h, wide
    # The encoder's pre-activation is kept only where a kernel makes it.
    kept = ic3net.saved_names(params, cfg)
    assert (ic3net.ENC_PRE in kept) == (path == "grouped")
    whys = " ".join(why for _, why in wide)
    assert "stop_gradient" not in whys and "logistic" not in whys, wide


@CASES
def test_remat_gradients_match_the_plain_step(groups, path, monkeypatch):
    env, ecfg, cfg, params, plans = _setup(groups, path, 32, 20)
    tcfg = mt.TrainConfig(batch=8)
    key = jax.random.PRNGKey(7)

    def loss_and_grads():
        f = jax.jit(lambda p, k: jax.value_and_grad(mt.a2c_loss, has_aux=True)(
            p, k, cfg, ecfg, tcfg, env, plans))
        with jax.default_matmul_precision("highest"):
            (loss, _), grads = f(params, key)
        return loss, grads

    loss, grads = loss_and_grads()
    monkeypatch.setattr(ic3net, "policy_step", ic3net._policy_step)
    ref_loss, ref_grads = loss_and_grads()
    assert float(loss) == float(ref_loss)
    for (path_, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree.leaves(ref_grads)):
        gap = float(jnp.linalg.norm(g - r))
        assert gap <= 1e-6 * float(jnp.linalg.norm(r)), (
            jax.tree_util.keystr(path_), gap)
