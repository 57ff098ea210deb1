"""Plain reference of IC3Net training on Predator-Prey, with FLGW layers.

Follows Singh et al. (ICLR'19) as LearningGroup (arXiv:2210.16624, §IV-A)
trains it: per-agent LSTM policy with weights shared over agents, a gated
mean of the other agents' communication vectors, REINFORCE with a value
baseline, RMSprop. Every projection but the value and gate heads is an FLGW
layer (``flgw.linear``). All arithmetic is float32; the matrix products
run at the highest precision, or for the control at three bfloat16 passes
(``product``).

Departures, each also made by the program under test: the gate head gets
no gradient (the gate regulariser is the mean of sampled gates, constant in
the weights), and the forget gate carries a fixed +1 bias.

The random draws (environment resets, actions, gates) use the same
``jax.random`` key splits as the program, so both sample the same
episodes from the same keys; nothing is imported from the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import flgw

FLGW_LAYERS = ("enc", "lstm_x", "lstm_h", "comm", "policy")
MOVES = np.array([[0, 0], [-1, 0], [1, 0], [0, -1], [0, 1]], np.int32)


def obs_dim(c) -> int:
    return 2 * c["env_size"] + (2 * c["vision"] + 1) ** 2 + 1


def layer_shapes(c) -> dict:
    h = c["hidden"]
    return {"enc": (obs_dim(c), h), "lstm_x": (h, 4 * h),
            "lstm_h": (h, 4 * h), "comm": (h, h),
            "policy": (h, c["n_actions"]), "value": (h, 1), "gate": (h, 2)}


def init_params(key, c):
    """Weights from one key: every projection ``N(0, 1/fan_in)``; the two
    output heads that sample (policy, gate) scaled by ``head_scale``, the
    usual small init of a policy's last layer; grouping scores ``N(0, 1)``;
    LSTM bias zero."""
    params = {}
    keys = jax.random.split(key, 2 * len(layer_shapes(c)))
    for i, (name, (m, n)) in enumerate(sorted(layer_shapes(c).items())):
        scale = c["head_scale"] if name in ("policy", "gate") else 1.0
        p = {"w": jax.random.normal(keys[2 * i], (m, n)) * (scale / m ** 0.5)}
        if name in FLGW_LAYERS and c["flgw_groups"] > 1:
            ki, ko = jax.random.split(keys[2 * i + 1])
            p["ig"] = jax.random.normal(ki, (m, c["flgw_groups"]))
            p["og"] = jax.random.normal(ko, (c["flgw_groups"], n))
        params[name] = p
    params["lstm_b"] = jnp.zeros((4 * c["hidden"],))
    return params


# --- Predator-Prey -------------------------------------------------------

def env_reset(key, c):
    kp, ka = jax.random.split(key)
    size = c["env_size"]
    prey = jax.random.randint(kp, (2,), 0, size, jnp.int32)
    pos = jax.random.randint(ka, (c["n_agents"], 2), 0, size, jnp.int32)
    return pos, prey, jnp.zeros((c["n_agents"],), bool)


def env_observe(pos, prey, c):
    size, v = c["env_size"], c["vision"]
    off = prey[None, :] - pos
    seen = jnp.all(jnp.abs(off) <= v, axis=1)
    w = 2 * v + 1
    cell = (off[:, 0] + v) * w + (off[:, 1] + v)
    prey_oh = jax.nn.one_hot(jnp.clip(cell, 0, w * w - 1), w * w) * seen[:, None]
    return jnp.concatenate(
        [jax.nn.one_hot(pos[:, 0], size), jax.nn.one_hot(pos[:, 1], size),
         prey_oh, seen[:, None].astype(jnp.float32)], axis=1)


def env_step(pos, prey, arrived, t, action, c):
    move = jnp.where(arrived[:, None], 0, jnp.asarray(MOVES)[action])
    pos = jnp.clip(pos + move, 0, c["env_size"] - 1)
    arrived = arrived | jnp.all(pos == prey[None, :], axis=1)
    reward = jnp.where(arrived, c["prey_reward"], c["step_penalty"])
    t = t + 1
    return pos, arrived, t, reward, jnp.all(arrived) | (t >= c["max_steps"])


# --- the network ---------------------------------------------------------

def product(precision: str):
    """The matrix product at ``precision``: "highest" (float32), or "high",
    three bfloat16 passes forward and backward (the high and low bfloat16
    parts of both operands, the product of the low parts left out), written
    out so that it means the same on every backend."""
    if precision == "highest":
        return functools.partial(jnp.matmul, precision="highest")
    assert precision == "high", precision

    def three(a, b):
        def split(x):
            # reduce_precision, unlike a round trip through bfloat16, is
            # not elided by XLA's excess-precision rewrites
            hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
            lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                          mantissa_bits=7)
            return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)
        (ah, al), (bh, bl) = split(a), split(b)
        dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)

    @jax.custom_vjp
    def mm(a, b):
        return three(a, b)

    def fwd(a, b):
        return three(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return three(g, jnp.swapaxes(b, -1, -2)), three(jnp.swapaxes(a, -1, -2), g)
    mm.defvjp(fwd, bwd)
    return mm


def _proj(p, x, c, mm):
    if "ig" in p:
        return flgw.linear(x, p["w"], p["ig"], p["og"],
                           c["capacity_slack"], c["ste_temperature"], mm)
    return mm(x, p["w"])


def policy_step(params, obs, h, cell, gate_prev, c, mm):
    """One step for all agents of one env."""
    a = c["n_agents"]
    cvec = _proj(params["comm"], jax.lax.stop_gradient(h), c, mm)
    cvec = cvec * gate_prev[:, None]
    comm_in = (cvec.sum(0, keepdims=True) - cvec) / max(a - 1, 1)
    x = jnp.tanh(_proj(params["enc"], obs, c, mm)) + comm_in
    gates = (_proj(params["lstm_x"], x, c, mm) + _proj(params["lstm_h"], h, c, mm)
             + params["lstm_b"])
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    cell = jax.nn.sigmoid(f + 1.0) * cell + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(cell)
    logits = _proj(params["policy"], h, c, mm)
    value = mm(h, params["value"]["w"])[:, 0]
    gate_logits = mm(h, params["gate"]["w"])
    return logits, value, gate_logits, h, cell


def rollout(params, key, c, mm, forced=None):
    """One episode of one env. Each step samples the agents' actions and
    gates; ``forced``, a pair of ``(max_steps, n_agents)`` arrays, takes
    them from there instead (a diagnostic, never part of a run)."""
    k_env, k_act = jax.random.split(key)
    pos, prey, arrived = env_reset(k_env, c)
    a, hid = c["n_agents"], c["hidden"]
    z = jnp.zeros((a, hid))

    def step(carry, xs):
        pos, arrived, t, h, cell, gate, done = carry
        k, decision = xs
        obs = env_observe(pos, prey, c)
        logits, value, gate_logits, h, cell = policy_step(
            params, obs, h, cell, gate, c, mm)
        action = jax.random.categorical(k, logits)
        kg, _ = jax.random.split(k)
        p_talk = jax.nn.softmax(gate_logits)[:, 1]
        new_gate = jax.random.bernoulli(kg, p_talk).astype(jnp.float32)
        if decision is not None:
            action, new_gate = decision[0], decision[1].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, action[:, None], 1)[:, 0]
        entropy = -jnp.sum(jax.nn.softmax(logits) * logp, axis=-1)
        npos, narrived, nt, reward, ndone = env_step(pos, prey, arrived, t,
                                                     action, c)
        reward = jnp.where(done, 0.0, reward)
        pos = jnp.where(done, pos, npos)
        arrived = jnp.where(done, arrived, narrived)
        t = jnp.where(done, t, nt)
        return ((pos, arrived, t, h, cell, new_gate, done | ndone),
                (reward, logp_a, value, entropy, new_gate, action))

    keys = jax.random.split(k_act, c["max_steps"])
    carry0 = (pos, arrived, jnp.zeros((), jnp.int32), z, z,
              jnp.ones((a,)), jnp.zeros((), bool))
    carry, outs = jax.lax.scan(step, carry0, (keys, forced))
    return outs


def a2c_loss(params, key, c, batch, mm, keep=1.0, forced=None):
    """Mean A2C loss over ``batch`` envs, and the actions and gates taken
    (each ``(envs, max_steps, n_agents)``); ``keep < 1`` averages over the
    first share of the envs only (the half-batch fault)."""
    keys = jax.random.split(key, batch)[:int(batch * keep)]
    rew, logp, val, ent, gates, actions = jax.vmap(
        lambda k, f: rollout(params, k, c, mm, f))(keys, forced)

    def disc(carry, r):
        carry = r + c["gamma"] * carry
        return carry, carry
    _, ret = jax.lax.scan(disc, jnp.zeros_like(rew[:, 0]),
                          rew[:, ::-1].swapaxes(0, 1))
    ret = ret[::-1].swapaxes(0, 1)
    adv = ret - val
    pg = -jnp.mean(logp * jax.lax.stop_gradient(adv))
    loss = (pg + c["value_coef"] * jnp.mean(adv ** 2)
            - c["entropy_coef"] * jnp.mean(ent)
            + c["gate_coef"] * jnp.mean(gates))
    return loss, (actions, gates)


def rmsprop(params, grads, nu, c):
    def upd(p, g, s):
        s = c["rmsprop_decay"] * s + (1 - c["rmsprop_decay"]) * g * g
        p = p - c["lr"] * g / (jnp.sqrt(s) + 1e-8)
        return p, s
    out = jax.tree.map(upd, params, grads, nu)
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            jax.tree.map(lambda o: o[1], out, is_leaf=is_pair))


@functools.partial(jax.jit, static_argnames=("cfg_items", "batch", "updates",
                                             "precision", "keep"))
def _train(params, key, forced, cfg_items, batch, updates, precision, keep):
    c = dict(cfg_items)
    mm = product(precision)
    nu = jax.tree.map(jnp.zeros_like, params)

    def body(carry, decisions):
        p, nu, key = carry
        key, k = jax.random.split(key)
        (loss, taken), g = jax.value_and_grad(a2c_loss, has_aux=True)(
            p, k, c, batch, mm, keep, decisions)
        p, nu = rmsprop(p, g, nu, c)
        return (p, nu, key), (loss, nu, taken)

    (p, _, _), (losses, nus, taken) = jax.lax.scan(
        body, (params, nu, key), forced, length=updates)
    return losses, nus, p, taken


def config_items(c: dict) -> tuple:
    """The configuration's scalar entries, hashable, for jit's static
    arguments."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def train(params, key, c, *, batch, updates, precision="highest", keep=1.0,
          forced=None):
    """``updates`` RMSprop updates from ``params`` and the chunk key ``key``
    (split once per update, as the program's scan does). Returns the loss
    of each update, the RMSprop state after each update (stacked on a
    leading axis), the parameters after the last, and the actions and
    gates each update took, ``(updates, batch, max_steps, n_agents)``.
    ``forced``, such a pair, makes every update take those instead of
    sampling its own."""
    items = config_items(c)
    if forced is not None:
        forced = tuple(jnp.asarray(x) for x in forced)
        # per env: (max_steps, 2, n_agents), scanned by step
        forced = jnp.stack(forced, axis=-2).astype(jnp.int32)
    return _train(params, key, forced, items, batch, updates, precision, keep)
