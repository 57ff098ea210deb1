"""IC3Net (Singh et al., '19) — the MARL network LearningGroup trains.

Per agent (weights shared across agents): an observation encoder, an LSTM
whose input is the encoded observation plus a gated mean of the other
agents' communication vectors, a discrete-action policy head, a value head,
and a communication gate head (the "learning when to communicate" part).

Every projection is a FLGW-capable ``dense`` layer — this network is where
the paper applies weight grouping (Fig. 4a/9): encoder, the 4H LSTM gate
matrices, the communication projection and the output heads all carry IG/OG
grouping matrices when ``flgw_groups > 1``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro import scopes
from repro.core import encoder
from repro.core.flgw import FLGWConfig
from repro.models.layers import dense_init, plan_of, proj, runs_kernel
from repro.sharding.partition import constrain

# What one policy step keeps for its backward (``policy_step``): the LSTM
# input and gate pre-activations, and the encoder's pre-activation where a
# kernel computes it. The rest is rebuilt from these and the step's inputs.
LSTM_IN = "lstm_in"
LSTM_GATES = "lstm_gates"
ENC_PRE = "enc_pre"


@dataclasses.dataclass(frozen=True)
class IC3NetConfig:
    hidden: int = 128
    n_agents: int = 3
    n_actions: int = 5
    obs_dim: int = 0              # filled from the env at init time
    flgw_groups: int = 1
    flgw_path: str = "masked"
    comm_detach: bool = True      # IC3Net detaches comm grads across agents

    @property
    def flgw(self) -> FLGWConfig | None:
        if self.flgw_groups <= 1:
            return None
        return FLGWConfig(groups=self.flgw_groups, path=self.flgw_path)


def init(key: jax.Array, cfg: IC3NetConfig):
    h = cfg.hidden
    ks = jax.random.split(key, 8)
    fl = cfg.flgw
    params, specs = {}, {}
    params["enc"], specs["enc"] = dense_init(
        ks[0], cfg.obs_dim, h, flgw=fl, axes=("in", "hidden"),
        dtype=jnp.float32)
    # LSTM: x (h) and hidden (h) -> 4 gates
    params["lstm_x"], specs["lstm_x"] = dense_init(
        ks[1], h, 4 * h, flgw=fl, axes=("hidden", "gates"),
        dtype=jnp.float32)
    params["lstm_h"], specs["lstm_h"] = dense_init(
        ks[2], h, 4 * h, flgw=fl, axes=("hidden", "gates"),
        dtype=jnp.float32)
    params["lstm_b"] = jnp.zeros((4 * h,), jnp.float32)
    specs["lstm_b"] = (None,)
    params["comm"], specs["comm"] = dense_init(
        ks[3], h, h, flgw=fl, axes=("hidden", "hidden"), dtype=jnp.float32)
    params["policy"], specs["policy"] = dense_init(
        ks[4], h, cfg.n_actions, flgw=fl, axes=("hidden", "out"),
        dtype=jnp.float32)
    params["value"], specs["value"] = dense_init(
        ks[5], h, 1, flgw=None, axes=("hidden", "out"), dtype=jnp.float32)
    params["gate"], specs["gate"] = dense_init(
        ks[6], h, 2, flgw=None, axes=("hidden", "out"), dtype=jnp.float32)
    return params, specs


def encode_plans(params, cfg: IC3NetConfig) -> encoder.PlanState:
    """One OSEL-analogue pass: the PlanState of every FLGW layer.

    Returns the empty PlanState unless the compact ``grouped`` path is
    active — the masked/dense paths never consume plans, and the empty
    state keeps the training-loop carry structure uniform across
    configurations.
    """
    fl = cfg.flgw
    if fl is None or fl.path != "grouped":
        return encoder.empty_state()
    return encoder.encode_plans(params, fl)


def flops_per_step(cfg: IC3NetConfig) -> float:
    """Dense-equivalent FLOPs of one forward ``policy_step`` (all agents).

    The same accounting the paper's Fig. 11 uses: 2·M·N per projection,
    summed over encoder, the two 4H LSTM gate matrices, the communication
    projection and the three heads.
    """
    h = cfg.hidden
    per_agent = 2 * (cfg.obs_dim * h          # encoder
                     + h * 4 * h * 2          # LSTM x/h gates
                     + h * h                  # comm projection
                     + h * cfg.n_actions + h + h * 2)  # policy/value/gate
    return float(cfg.n_agents * per_agent)


def lstm_cell(params, cfg: IC3NetConfig, x, hc, plans=None):
    h, c = hc
    fl = cfg.flgw
    gates = proj(params["lstm_x"], x, fl, plan=plan_of(plans, "lstm_x")) \
        + proj(params["lstm_h"], h, fl, plan=plan_of(plans, "lstm_h")) \
        + params["lstm_b"]
    gates = checkpoint_name(gates, LSTM_GATES)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def policy_step(params, cfg: IC3NetConfig, obs, hc, gate_prev, plans=None):
    """One communication+action step for all agents of one env.

    obs: (A, obs_dim); hc: ((A,H),(A,H)); gate_prev: (A,) float in [0,1] —
    the previous step's communication gate decision per agent.
    ``plans``: cached sparse metadata from :func:`encode_plans` (grouped
    path); ``None``/``{}`` re-encodes inside each projection.
    Returns (action_logits (A,n_act), value (A,), gate_logits (A,2), new_hc).

    Rematerialised: the backward keeps only the names of
    :func:`saved_names` and the step's inputs, and recomputes the rest on
    the vector units. A scan over steps then stacks 7 H-wide buffers per
    agent and step (8 with a grouped encoder) besides the observation,
    instead of 17. No LSTM, communication or head product runs twice, and
    the forward is unchanged.
    """
    step = jax.checkpoint(
        lambda p, o, s, g, pl: _policy_step(p, cfg, o, s, g, pl),
        prevent_cse=False,    # called inside lax.scan
        policy=jax.checkpoint_policies.save_only_these_names(
            *saved_names(params, cfg)))
    with jax.named_scope(scopes.POLICY):
        return step(params, obs, hc, gate_prev, plans)


def saved_names(params, cfg: IC3NetConfig) -> tuple[str, ...]:
    """Residual names :func:`policy_step` keeps. The encoder's
    pre-activation is kept only where a kernel computes it: rebuilding it
    would run the kernel again, while a plain K=obs_dim product is cheaper
    to redo than to store."""
    names = (LSTM_IN, LSTM_GATES)
    if runs_kernel(params["enc"], cfg.flgw):
        names += (ENC_PRE,)
    return names


def _policy_step(params, cfg: IC3NetConfig, obs, hc, gate_prev, plans):
    a = cfg.n_agents
    fl = cfg.flgw
    h, c = hc
    # Mesh path: per-agent work shards over the "agent" axis (no-op hints
    # off the mesh — see repro.sharding.partition.constrain). The gated
    # mean below is the one cross-agent reduction: on an agent-sharded
    # mesh it is the communication all-reduce, everything else is local.
    obs = constrain(obs, ("agent", None))
    with jax.named_scope(scopes.COMM):
        comm_src = jax.lax.stop_gradient(h) if cfg.comm_detach else h
        cvec = proj(params["comm"], comm_src, fl,
                    plan=plan_of(plans, "comm"))         # (A, H)
        cvec = cvec * gate_prev[:, None]
        # gated mean over the *other* agents
        total = jnp.sum(cvec, axis=0, keepdims=True)
        denom = max(a - 1, 1)
        comm_in = (total - cvec) / denom                  # (A, H)
    with jax.named_scope(scopes.ENCODER):
        # Named before the tanh: the tanh's derivative reads its output
        # before any name after it applies, so only its input can be kept.
        e = jnp.tanh(checkpoint_name(
            proj(params["enc"], obs, fl, plan=plan_of(plans, "enc")),
            ENC_PRE))
    x = checkpoint_name(constrain(e + comm_in, ("agent", None)), LSTM_IN)
    with jax.named_scope(scopes.LSTM):
        h, c = lstm_cell(params, cfg, x, (h, c), plans)
    h = constrain(h, ("agent", None))
    with jax.named_scope(scopes.HEADS):
        logits = proj(params["policy"], h, fl,
                      plan=plan_of(plans, "policy"))
        value = proj(params["value"], h)[:, 0]
        gate_logits = proj(params["gate"], h)
    return logits, value, gate_logits, (h, c)


def initial_state(cfg: IC3NetConfig):
    z = jnp.zeros((cfg.n_agents, cfg.hidden), jnp.float32)
    return (z, z), jnp.ones((cfg.n_agents,), jnp.float32)
