"""On-device multi-scenario MARL training engine (REINFORCE/A2C + FLGW).

Reproduces the paper's algorithm-validation setup (§IV-A) — IC3Net with
RMSprop lr=1e-3, B parallel environments per iteration, success rate as the
accuracy metric — but generalized along the two axes the paper credits for
its speedup and scope:

* **any registered environment** (``repro.marl.envs``): the loop is written
  against the functional ``Env`` protocol, so Predator-Prey, Traffic
  Junction and Spread (and future scenarios) share one engine;
* **fully on device**: iterations run inside a ``jax.lax.scan`` — the host
  never syncs per step. Metrics are accumulated on device and fetched once
  per log window, mirroring the paper's "fully on-chip training" (the FPGA
  never round-trips to a host between iterations). Scale-out runs the same
  scan under ``jit`` on a 2-D ``("env", "agent")`` ``jax.sharding`` mesh
  (``TrainConfig.mesh``; ``repro.launch.mesh.make_marl_mesh``): the rollout
  batch shards over ``env``, per-agent activations over ``agent``, and the
  learner state stays replicated (IC3Net weights are agent-shared). The
  retired ``pmap`` path survives as the deprecated ``TrainConfig.parallel``
  alias, which routes to a 1-D env-only mesh.

A FLGW sparsity schedule (``repro.core.schedule.SparsitySchedule``) threads
through the loop: during ``warmup_steps`` the network trains dense, then the
grouping mask switches on — the G ramp the schedule describes. (G itself is
static: IG/OG shapes depend on it.)
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import kernels as kernels_mod
from repro import scopes
from repro.core import encoder, flgw, grouped
from repro.core.schedule import SparsitySchedule
from repro.launch.mesh import make_marl_mesh
from repro.marl import envs as envs_mod
from repro.marl import ic3net
from repro.optim.optimizers import rmsprop, rmsprop_init
from repro.sharding import partition
from repro.sharding.partition import constrain


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    batch: int = 16               # parallel envs (paper: B ∈ 1..32)
    lr: float = 1e-3              # paper: RMSprop 0.001
    gamma: float = 0.99
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    gate_coef: float = 0.01       # IC3Net gate regularizer
    # (env, agent) shard counts of the jax.sharding mesh path; env <= 0
    # auto-fills with whatever devices the agent axis leaves free. None
    # keeps the single-device scan. ``batch`` is the GLOBAL env batch,
    # sharded over the env axis (the retired pmap path rolled out
    # ``batch`` envs per device — multiply by the old device count when
    # migrating).
    mesh: Optional[tuple] = None
    # DEPRECATED: the old pmap data-parallel switch. Routes to a 1-D
    # env-only mesh (mesh=(local_device_count, 1)); set ``mesh`` instead.
    parallel: bool = False


def _policy_terms(logits, gate_logits, action, new_gate):
    """Per-step loss terms from one policy forward + the realised actions.

    Shared by the on-policy :func:`rollout` and the async learner's replay
    (``repro.marl.async_train.replay_terms``): both must derive the exact
    same (logp, entropy, gate_logp) ops from (logits, gate_logits), or the
    decoupled pipeline could never be bitwise-checked against the
    synchronous scan. ``action``/``new_gate`` are the realised (sampled or
    replayed) decisions — integers/0-1 floats, no gradient flows into
    them.
    """
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, action[:, None], 1)[:, 0]
    entropy = -jnp.sum(jax.nn.softmax(logits) * logp, axis=-1)
    gate_logp = jax.nn.log_softmax(gate_logits)[:, 1] * new_gate
    return logp_a, entropy, gate_logp


def rollout(params, key, cfg: ic3net.IC3NetConfig, ecfg, env: envs_mod.Env,
            plans=None, collect: bool = False):
    """One full episode for one env. Returns per-step tensors + success.

    ``collect=True`` (the async actor path) additionally returns the raw
    ``(obs, action)`` sequences so a learner process can re-unroll the
    policy over the stored trajectory — the sampled gates already ride the
    default outputs. The default graph is unchanged: the extra stacking
    only exists when requested.
    """
    k_env, k_act = jax.random.split(key)
    with jax.named_scope(scopes.ENV):
        state = env.reset(k_env, ecfg)
    hc, gate = ic3net.initial_state(cfg)

    def step_fn(carry, k):
        state, hc, gate, done = carry
        with jax.named_scope(scopes.ENV):
            obs = env.observe(state, ecfg)
        logits, value, gate_logits, hc = ic3net.policy_step(
            params, cfg, obs, hc, gate, plans)
        with jax.named_scope(scopes.SAMPLE):
            action = jax.random.categorical(k, logits)          # (A,)
            kg, _ = jax.random.split(k)
            new_gate = jax.random.bernoulli(
                kg, jax.nn.softmax(gate_logits)[:, 1]).astype(jnp.float32)
            logp_a, entropy, gate_logp = _policy_terms(
                logits, gate_logits, action, new_gate)
        with jax.named_scope(scopes.ENV):
            nstate, reward, ndone = env.step(state, action, ecfg)
            # freeze transitions after done
            reward = jnp.where(done, 0.0, reward)
            nstate = jax.tree.map(
                lambda a, b: jnp.where(done, a, b), state, nstate)
        out = (reward, logp_a, value, entropy, gate_logp, new_gate)
        if collect:
            out = out + (obs, action)
        return (nstate, hc, new_gate, done | ndone), out

    keys = jax.random.split(k_act, ecfg.max_steps)
    with jax.named_scope(scopes.ROLLOUT):
        (state, _, _, _), outs = jax.lax.scan(
            step_fn, (state, hc, gate, jnp.zeros((), bool)), keys)
    with jax.named_scope(scopes.ENV):
        succ = env.success(state)
    return outs + (succ,)


def a2c_terms(rew, logp, val, ent, gate_logp, gates, succ,
              tcfg: TrainConfig):
    """A2C loss + metrics from per-step trajectory tensors, all (B, T, A).

    The loss core shared by the synchronous path (:func:`a2c_loss`, which
    differentiates through the rollout that produced the tensors) and the
    async learner (``repro.marl.async_train``, which differentiates
    through a replay of a stored trajectory): discounted returns-to-go,
    advantage policy gradient, value regression, entropy and gate
    regularizers. Gradients flow through ``logp``/``val``/``ent``/
    ``gate_logp``; ``rew``/``gates``/``succ`` are data.
    """
    def disc(carry, r):
        carry = r + tcfg.gamma * carry
        return carry, carry
    with jax.named_scope(scopes.A2C):
        _, returns = jax.lax.scan(disc, jnp.zeros_like(rew[:, 0]),
                                  rew[:, ::-1].swapaxes(0, 1))
        returns = returns[::-1].swapaxes(0, 1)                # (B, T, A)
        adv = returns - val
        pg = -jnp.mean(logp * jax.lax.stop_gradient(adv))
        vloss = jnp.mean(adv ** 2)
        eloss = -jnp.mean(ent)
        gloss = jnp.mean(gates)                               # talk less
        loss = pg + tcfg.value_coef * vloss + tcfg.entropy_coef * eloss \
            + tcfg.gate_coef * gloss
        return loss, {"success": jnp.mean(succ.astype(jnp.float32)),
                      "return": jnp.mean(jnp.sum(rew, axis=1)),
                      "loss": loss}


def a2c_loss(params, key, cfg, ecfg, tcfg: TrainConfig, env: envs_mod.Env,
             plans=None):
    keys = jax.random.split(key, tcfg.batch)
    # Mesh path: the rollout batch is the env-axis workload. The logical
    # constraints are inert (no-ops) unless tracing happens under
    # partition.use_constraints(mesh) — single-device runs never pay them.
    keys = constrain(keys, ("env",) + (None,) * (keys.ndim - 1))
    rew, logp, val, ent, gate_logp, gates, succ = jax.vmap(
        lambda k: rollout(params, k, cfg, ecfg, env, plans))(keys)
    rew, logp, val, ent = (constrain(t, ("env", None, "agent"))
                           for t in (rew, logp, val, ent))
    return a2c_terms(rew, logp, val, ent, gate_logp, gates, succ, tcfg)


def _mean_mask_sparsity(params, cfg: ic3net.IC3NetConfig) -> jax.Array:
    """Mean realised mask sparsity over the FLGW layers (0 when dense)."""
    fl = cfg.flgw
    if fl is None:
        return jnp.zeros(())
    vals = [flgw.mask_sparsity(*flgw.grouping_indices(p["ig"], p["og"]),
                               fl.groups)
            for _, p in grouped.iter_flgw_layers(params)]
    return jnp.mean(jnp.stack(vals)) if vals else jnp.zeros(())


def maybe_refresh_plans(params, plans, it, cfg: ic3net.IC3NetConfig,
                        schedule: Optional[SparsitySchedule]):
    """Amortized OSEL refresh — a thin delegate to the one implementation.

    :func:`repro.core.encoder.maybe_refresh` owns the whole policy (fixed
    period, change-driven signature compare, hybrid staleness bound;
    ``lax.cond`` inside, so ``it`` may be a traced int32; empty PlanStates
    pass through untouched). The sync scan carry, the host-loop mirror
    and the async learner loop (``repro.marl.async_train``) all call this
    same delegate — any refresh-behavior divergence between the three
    loops is a bug, pinned by ``test_maybe_refresh_plans_is_pure_delegate``.
    This function adds nothing beyond unwrapping ``cfg.flgw``.
    """
    return encoder.maybe_refresh(params, plans, it, cfg.flgw, schedule)


def _loss_grads(params, key, it, cfg, ecfg, tcfg, env,
                schedule: Optional[SparsitySchedule], plans=None):
    """(metrics, grads) at global iteration ``it`` (traced int32).

    With a schedule, the first ``warmup_steps`` iterations run the dense
    path (mask off) via ``lax.cond`` — both branches share the same param
    tree, so the G ramp happens inside the compiled loop. ``plans`` is the
    cached sparse metadata consumed by the grouped path.
    """
    def vag(c):
        def f(p, k):
            return jax.value_and_grad(a2c_loss, has_aux=True)(
                p, k, c, ecfg, tcfg, env, plans)
        return f

    ramped = (schedule is not None and schedule.warmup_steps > 0
              and cfg.flgw is not None)
    if ramped:
        dense_cfg = dataclasses.replace(cfg, flgw_path="dense")
        (_, metrics), grads = jax.lax.cond(
            schedule.sparse_at(it), vag(cfg), vag(dense_cfg), params, key)
    else:
        (_, metrics), grads = vag(cfg)(params, key)
    metrics = dict(metrics)
    # report the sparsity of the compute that actually ran: 0 on warmup
    # iterations, where the dense branch executed full FLOPs
    sparsity = _mean_mask_sparsity(params, cfg)
    if ramped:
        sparsity = jnp.where(schedule.sparse_at(it), sparsity, 0.0)
    metrics["mask_sparsity"] = sparsity
    return metrics, grads


@partial(jax.jit, static_argnames=("cfg", "ecfg", "tcfg", "env", "schedule"))
def train_step(params, opt_state, key, cfg, ecfg, tcfg: TrainConfig,
               env: envs_mod.Env = None, schedule=None,
               it: jax.Array | int = 0, plans=None):
    """One host-driven update (seed-compatible API; used for parity tests)."""
    env = env or envs_mod.PREDATOR_PREY
    metrics, grads = _loss_grads(params, key, jnp.asarray(it, jnp.int32),
                                 cfg, ecfg, tcfg, env, schedule, plans)
    params, opt_state = rmsprop(params, grads, opt_state, lr=tcfg.lr)
    return params, opt_state, metrics


def _scan_chunk(params, opt_state, key, plans, start, n, cfg, ecfg, tcfg,
                env, schedule):
    """``n`` update iterations as one on-device ``lax.scan``.

    The FLGW plan cache rides in the carry: each iteration first passes
    through ``maybe_refresh_plans`` — a ``lax.cond`` that re-encodes the
    sparse metadata every ``schedule.refresh_every`` steps and reuses the
    carried (stale) plans otherwise, so the grouped Pallas kernel runs
    against amortized metadata inside the compiled loop.

    The same function serves the single-device path (``_train_chunk``) and
    the mesh path (``make_mesh_chunk``): under a mesh, GSPMD partitions the
    rollout from the logical constraints in ``a2c_loss`` /
    ``ic3net.policy_step`` — no pmean, no per-device key folding, just one
    global program. Returns stacked per-iteration metrics; the host
    fetches them once per log window instead of syncing every step.
    """
    def body(carry, it):
        params, opt_state, key, plans = carry
        plans = maybe_refresh_plans(params, plans, it, cfg, schedule)
        key, k = jax.random.split(key)
        metrics, grads = _loss_grads(params, k, it, cfg, ecfg, tcfg, env,
                                     schedule, plans)
        params, opt_state = rmsprop(params, grads, opt_state, lr=tcfg.lr)
        return (params, opt_state, key, plans), metrics

    its = start + jnp.arange(n, dtype=jnp.int32)
    (params, opt_state, key, plans), metrics = jax.lax.scan(
        body, (params, opt_state, key, plans), its)
    return params, opt_state, key, plans, metrics


_CHUNK_STATICS = ("n", "cfg", "ecfg", "tcfg", "env", "schedule")

_train_chunk = partial(jax.jit, static_argnames=_CHUNK_STATICS)(_scan_chunk)


@functools.lru_cache(maxsize=None)   # one jit (+its trace cache) per mesh
def make_mesh_chunk(mesh: Mesh):
    """jit of ``_scan_chunk`` for the 2-D ``("env", "agent")`` mesh path.

    The learner state (params / optimizer state / plan cache / PRNG key)
    is pinned replicated via ``in_shardings``/``out_shardings`` — IC3Net
    shares weights across agents, so there is nothing per-agent to shard
    in the state. The rollout work partitions instead: the env batch over
    ``env`` and per-agent activations over ``agent``, from the logical
    ``with_sharding_constraint`` hints that become active when the call is
    traced under ``partition.use_constraints(mesh)`` (see ``train``).

    One global program replaces the retired pmap path: the batch is the
    global batch (not per-device), keys are not folded per device, and on
    a (1, 1) mesh the computation is identical to ``_train_chunk`` — the
    parity tests pin that against the host loop.
    """
    repl = NamedSharding(mesh, P())
    return partial(jax.jit, static_argnames=_CHUNK_STATICS,
                   in_shardings=(repl, repl, repl, repl, repl),
                   out_shardings=repl)(_scan_chunk)


def _resolve_mesh(tcfg: TrainConfig) -> Optional[Mesh]:
    """TrainConfig -> Mesh (or None for the plain single-device scan)."""
    shape = tcfg.mesh
    if tcfg.parallel:
        routing = (
            "parallel=True now routes to a 1-D env-only mesh "
            "(mesh=(local_device_count, 1)) where ``batch`` is the GLOBAL "
            "env batch" if shape is None else
            f"the explicit TrainConfig.mesh={shape} wins and parallel=True "
            "is ignored")
        warnings.warn(
            "TrainConfig.parallel is deprecated: the pmap data-parallel "
            f"path was replaced by the jax.sharding mesh engine. {routing};"
            " set TrainConfig.mesh=(env, agent) explicitly.",
            DeprecationWarning, stacklevel=3)
        if shape is None:
            shape = (jax.local_device_count(), 1)
    if shape is None:
        return None
    env_shards, agent_shards = shape
    return make_marl_mesh(env=env_shards, agent=agent_shards)


@contextlib.contextmanager
def _mesh_contexts(mesh: Mesh):
    """Contexts active while tracing/running a mesh chunk.

    ``use_constraints`` switches the logical sharding hints on. On a
    multi-device mesh the FLGW Pallas kernels lower via the shared
    reference impl (``repro.kernels.use_reference_impl``): GSPMD cannot
    partition a pallas custom call — it would replicate the kernel on
    every shard — while the mathematically identical jnp reference shards
    like any einsum (same rationale as ``launch/dryrun``). A (1, 1) mesh
    keeps the kernels, preserving bitwise parity with the scan path.
    """
    ref = (kernels_mod.use_reference_impl if mesh.devices.size > 1
           else contextlib.nullcontext)
    with mesh, partition.use_constraints(mesh), ref():
        yield


_encode_plans = partial(jax.jit, static_argnames=("cfg",))(
    ic3net.encode_plans)

# host-loop mirror of the in-scan refresh: one jitted maybe_refresh keeps
# the host loop bit-identical to the scan carry under every refresh mode
_refresh_plans = partial(jax.jit, static_argnames=("cfg", "schedule"))(
    maybe_refresh_plans)


def _init(cfg, ecfg, env, seed):
    cfg = dataclasses.replace(cfg, obs_dim=env.obs_dim(ecfg),
                              n_agents=ecfg.n_agents,
                              n_actions=env.n_actions(ecfg))
    key = jax.random.PRNGKey(seed)
    kinit, key = jax.random.split(key)
    params, _ = ic3net.init(kinit, cfg)
    return cfg, key, params, rmsprop_init(params)


def train(cfg: ic3net.IC3NetConfig, ecfg=None, tcfg: TrainConfig = None,
          iterations: int = 100, seed: int = 0, log_every: int = 0,
          env: str | envs_mod.Env = "predator_prey",
          schedule: Optional[SparsitySchedule] = None,
          host_loop: bool = False):
    """Train IC3Net on a registered environment; returns (params, history).

    ``history`` is one dict of floats per iteration: success/return/loss,
    the realised ``mask_sparsity``, and host-derived throughput —
    ``steps_per_s`` (training iterations/s), ``env_steps_per_s`` and
    estimated ``sparse_gflops`` (dense-equivalent FLOPs scaled by the
    measured mask sparsity over measured wall time; the first window of
    the scan path includes compile time).
    The default path scans whole log windows on device; with
    ``tcfg.mesh=(env, agent)`` the same scan runs under ``jit`` on a
    ``jax.sharding`` mesh — rollout batch sharded over ``env``, per-agent
    activations over ``agent``, learner state replicated (``tcfg.batch``
    stays the *global* batch). ``host_loop=True`` drives one jitted
    update per iteration from Python (the seed loop, kept for parity
    testing and debugging; it ignores the mesh).
    """
    if isinstance(env, str):
        env = envs_mod.get(env)
    if ecfg is None:
        ecfg = env.config_cls()
    tcfg = tcfg or TrainConfig()
    mesh = None if host_loop else _resolve_mesh(tcfg)
    cfg, key, params, opt_state = _init(cfg, ecfg, env, seed)
    # plan cache: encoded once here, then refreshed inside the loop every
    # schedule.refresh_every iterations ({} when the grouped path is off)
    plans = _encode_plans(params, cfg)
    history: list[dict] = []
    # fwd + ~2x bwd dense-equivalent FLOPs of one training iteration
    # (tcfg.batch is the global env batch on every path)
    flops_iter = (3 * tcfg.batch * ecfg.max_steps
                  * ic3net.flops_per_step(cfg))

    def throughput(ms: dict, n_iters: int, dt: float) -> dict:
        rate = n_iters / max(dt, 1e-9)
        return {
            "steps_per_s": rate,
            "env_steps_per_s": rate * tcfg.batch * ecfg.max_steps,
            "sparse_gflops": rate * flops_iter
            * (1.0 - ms.get("mask_sparsity", 0.0)) / 1e9,
        }

    if host_loop:
        for it in range(iterations):
            if plans:
                plans = _refresh_plans(params, plans, it, cfg=cfg,
                                       schedule=schedule)
            key, k = jax.random.split(key)
            t0 = time.perf_counter()
            params, opt_state, metrics = train_step(
                params, opt_state, k, cfg, ecfg, tcfg, env, schedule, it,
                plans)
            ms = {k2: float(v) for k2, v in metrics.items()}
            ms.update(throughput(ms, 1, time.perf_counter() - t0))
            history.append(ms)
            if log_every and it % log_every == 0:
                print(f"iter {it:5d} success {history[-1]['success']:.3f} "
                      f"return {history[-1]['return']:.3f}")
        return params, history

    mesh_chunk = None if mesh is None else make_mesh_chunk(mesh)

    window = log_every if log_every > 0 else min(max(iterations, 1), 100)
    start = 0
    while start < iterations:
        n = min(window, iterations - start)
        t0 = time.perf_counter()
        if mesh_chunk is not None:
            with _mesh_contexts(mesh):
                params, opt_state, key, plans, metrics = mesh_chunk(
                    params, opt_state, key, plans,
                    jnp.asarray(start, jnp.int32), n,
                    cfg, ecfg, tcfg, env, schedule)
        else:
            params, opt_state, key, plans, metrics = _train_chunk(
                params, opt_state, key, plans,
                jnp.asarray(start, jnp.int32), n,
                cfg, ecfg, tcfg, env, schedule)
        fetched = {k2: np.asarray(v) for k2, v in metrics.items()}  # 1 sync
        dt = time.perf_counter() - t0
        for i in range(n):
            ms = {k2: float(v[i]) for k2, v in fetched.items()}
            ms.update(throughput(ms, n, dt))
            history.append(ms)
        if log_every:
            print(f"iter {start:5d} success {history[start]['success']:.3f} "
                  f"return {history[start]['return']:.3f}")
        start += n

    return params, history
