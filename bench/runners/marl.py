"""Runner of multi-agent RL training cells.

The program under test is the on-device training engine's jitted scan
chunk, ``repro.marl.train._train_chunk``: ``window_updates`` updates per
dispatch, dispatched back to back as ``repro.marl.train.train``
does, each dispatch ending in the fetch of its metrics. Set-up makes the
weights from the seed on the device in one jitted call and drives the
compiled chunk through its first dispatches: those updates are what
``correct`` compares with the reference, and they leave every shape of the
window compiled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench import flops, harness
from bench.reference import ic3net as ref
from repro.core.schedule import SparsitySchedule
from repro.marl import envs, ic3net
from repro.marl import train as mt
from repro.optim.optimizers import rmsprop_init

# The control: the reference in the program's place with its products at
# three bfloat16 passes, the precision below the configuration's float32 at
# the highest.
CONTROL = {"precision": "high"}


def faults(t: dict) -> dict:
    """Faults planted in the reference put in the program's place."""
    return {"half_batch": {"keep": 0.5}}


def _program_config(c: dict, t: dict):
    """The chunk's static arguments for this configuration and traffic:
    (model config, env config, train config, env, schedule)."""
    env = envs.get(c["env"])
    ecfg = env.config_cls(n_agents=c["n_agents"], size=c["env_size"],
                          vision=c["vision"], max_steps=c["max_steps"],
                          step_penalty=c["step_penalty"],
                          prey_reward=c["prey_reward"])
    cfg = ic3net.IC3NetConfig(hidden=c["hidden"], n_agents=c["n_agents"],
                              n_actions=c["n_actions"], obs_dim=ref.obs_dim(c),
                              flgw_groups=c["flgw_groups"],
                              flgw_path=c["flgw_path"])
    tcfg = mt.TrainConfig(batch=t["batch"], lr=c["lr"], gamma=c["gamma"],
                          value_coef=c["value_coef"],
                          entropy_coef=c["entropy_coef"],
                          gate_coef=c["gate_coef"])
    schedule = (SparsitySchedule(groups=c["flgw_groups"],
                                 refresh_every=t["refresh_every"])
                if c["flgw_groups"] > 1 else None)
    return cfg, ecfg, tcfg, env, schedule


def compared_dispatches(t: dict) -> int:
    """Whole dispatches that cover the compared updates."""
    return -(-t["compare_updates"] // t["window_updates"])


class Program:
    """The compiled chunk and its carried state, brought up from a seed."""

    def __init__(self, c: dict, t: dict, seed: int):
        self.t = t
        self.precision = c["matmul_precision"]
        self.statics = _program_config(c, t)
        key_w, self.key = jax.random.split(harness.seed_key(seed))
        self.params0 = jax.jit(lambda k: ref.init_params(k, c))(key_w)
        self.params = self.params0
        self.opt = jax.jit(rmsprop_init)(self.params)
        self.plans = jax.jit(ic3net.encode_plans, static_argnums=1)(
            self.params, self.statics[0])
        self.start = 0

    def dispatch(self) -> dict:
        """One dispatch of ``window_updates`` updates; returns its metrics
        on the host."""
        n = self.t["window_updates"]
        with jax.default_matmul_precision(self.precision):
            (self.params, self.opt, self.key, self.plans,
             metrics) = mt._train_chunk(self.params, self.opt, self.key,
                                        self.plans, jnp.asarray(self.start,
                                                                jnp.int32),
                                        n, *self.statics)
        self.start += n
        return {k: np.asarray(v) for k, v in metrics.items()}

    def window_call(self) -> list:
        return self.dispatch()["loss"].tolist()

    def first_steps(self) -> dict:
        """Drive the compared dispatches and read each update's loss, the
        RMSprop second moment after the first dispatch (for one update a
        dispatch, the first gradient) and each leaf's change."""
        losses, grad = [], None
        for _ in range(compared_dispatches(self.t)):
            losses.extend(self.window_call())
            if grad is None:
                grad = harness.leaf_norms(jax.tree.map(jnp.sqrt, self.opt))
        delta = harness.leaf_norms(jax.tree.map(jnp.subtract, self.params,
                                                self.params0))
        return {"loss": losses[:self.t["compare_updates"]], "losses": losses,
                "grad": grad, "delta": delta}


def program_observables(c: dict, t: dict, seed: int) -> dict:
    return Program(c, t, seed).first_steps()


def reference_observables(c: dict, t: dict, seed: int, *,
                          precision: str = "highest", keep: float = 1.0,
                          forced=None) -> dict:
    """The same readings from the plain reference (or, with another
    ``precision`` or ``keep``, from the control or a planted fault), and under
    ``taken`` the actions and gates of each update. ``forced`` makes the
    reference take given actions and gates (a diagnostic)."""
    key_w, key = jax.random.split(harness.seed_key(seed))
    p0 = jax.jit(lambda k: ref.init_params(k, c))(key_w)
    updates = compared_dispatches(t) * t["window_updates"]
    losses, nus, params, taken = ref.train(p0, key, c, batch=t["batch"],
                                           updates=updates,
                                           precision=precision, keep=keep,
                                           forced=forced)
    first = jax.tree.map(lambda x: jnp.sqrt(x[t["window_updates"] - 1]), nus)
    return {"loss": [float(x) for x in losses[:t["compare_updates"]]],
            "losses": [float(x) for x in losses],
            "grad": harness.leaf_norms(first),
            "delta": harness.leaf_norms(jax.tree.map(jnp.subtract, params,
                                                     p0)),
            "taken": taken}


def rehearse(c: dict, t: dict, devices) -> dict:
    """Compile, for the described ``devices``, the program's chunk and the
    reference's updates at the cell's shapes."""
    statics = _program_config(c, t)
    sharding = SingleDeviceSharding(devices[0])

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: ref.init_params(k, c), key)
    plans = jax.eval_shape(lambda p: ic3net.encode_plans(p, statics[0]),
                           params)
    args = spec((params, jax.eval_shape(rmsprop_init, params),
                 jax.eval_shape(lambda: key), plans,
                 jax.ShapeDtypeStruct((), jnp.int32)))
    n = t["window_updates"]
    with jax.default_matmul_precision(c["matmul_precision"]):
        out = {"program chunk":
               mt._train_chunk.lower(*args, n, *statics).compile()}
    out["reference updates"] = ref._train.lower(
        args[0], args[2], None, ref.config_items(c), t["batch"],
        compared_dispatches(t) * n, "highest", 1.0).compile()
    return out


def run(run: harness.Run) -> dict:
    c, t = run.config, run.traffic
    devs = harness.devices(run)
    env_steps = t["batch"] * c["max_steps"]

    def rates(updates, elapsed):
        return {"env_steps_per_s": updates * env_steps / elapsed}
    return harness.execute(
        run, devs, Program(c, t, run.seed),
        lambda: reference_observables(c, t, run.seed),
        {"update_ops": flops.ic3net_update(c, t["batch"])}, rates)
