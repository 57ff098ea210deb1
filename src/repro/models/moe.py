"""Token-choice top-k Mixture-of-Experts: a router over every expert, the
experts this chip holds, and optional shared experts.

The router keeps its full width (``n_experts`` softmax scores a token) and
picks the top ``top_k``; the layer computes the part of the result that
its held experts ``[expert_first, expert_first + n_held)`` give, plus the
shared experts' SwiGLU on every token. With every expert held that is the
whole layer; with a share (expert parallelism, DeepSeek-V2 §2.2), what the
absent experts would add comes from the chips that hold them.

Two dispatches:

* dropless (``capacity_factor`` 0, and decode): the assignments to held
  experts sorted by expert and multiplied by ``jax.lax.ragged_dot``; on
  the TPU its kernels' row grid is sized at run time by the tiles the
  groups fill, so the work follows the held assignments and nothing is
  dropped under uneven load;
* capacity-bounded (training of the configurations that set a capacity,
  and every FLGW-grouped expert layer): gather-based, megablocks-style —
  the first ``capacity`` assignments per expert, a batched per-expert FFN
  einsum, and a scatter back.

Expert weights are stacked (E, ...) so the expert axis shards over the
mesh (EP). This mirrors the FLGW compact path in ``repro.core.grouped`` —
the router plays the role of the IG matrix, the expert axis the role of
groups.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core.flgw import FLGWConfig, init_grouping
from repro.models.layers import ACTIVATIONS, mlp, mlp_init, plan_of, proj


def moe_init(key, cfg, *, flgw: Optional[FLGWConfig] = None):
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    e = cfg.n_held
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    params = {
        "router": (jax.random.normal(ks[0], (d, cfg.n_experts), jnp.float32)
                   * std).astype(jnp.float32),
        "up": {"w": (jax.random.normal(ks[1], (e, d, ff), jnp.float32) * std
                     ).astype(cfg.dtype)},
        "gate": {"w": (jax.random.normal(ks[2], (e, d, ff), jnp.float32) * std
                       ).astype(cfg.dtype)},
        "down": {"w": (jax.random.normal(ks[3], (e, ff, d), jnp.float32)
                       * ff ** -0.5).astype(cfg.dtype)},
    }
    specs = {
        "router": ("embed", None),
        "up": {"w": ("expert", "embed", "ffn")},
        "gate": {"w": ("expert", "embed", "ffn")},
        "down": {"w": ("expert", "ffn", "embed")},
    }
    if flgw is not None and flgw.groups > 1:
        # FLGW composes per-expert: one IG/OG pair per expert FFN projection.
        gk = jax.random.split(ks[4], 3)
        for i, name in enumerate(("up", "gate")):
            g = jax.vmap(lambda k: init_grouping(k, d, ff, flgw.groups))(
                jax.random.split(gk[i], e))
            params[name]["ig"], params[name]["og"] = g["ig"], g["og"]
            specs[name]["ig"] = ("expert", "embed", None)
            specs[name]["og"] = ("expert", None, "ffn")
        g = jax.vmap(lambda k: init_grouping(k, ff, d, flgw.groups))(
            jax.random.split(gk[2], e))
        params["down"]["ig"], params["down"]["og"] = g["ig"], g["og"]
        specs["down"]["ig"] = ("expert", "ffn", None)
        specs["down"]["og"] = ("expert", None, "embed")
    if cfg.shared_d_ff:
        # a key of its own, so layers without shared experts keep theirs
        params["shared"], specs["shared"] = mlp_init(
            jax.random.fold_in(key, 5), d, cfg.shared_d_ff, dtype=cfg.dtype)
    return params, specs


def _expert_ffn(p, xe, flgw, plans=None, act="gelu_tanh"):
    """xe: (E, C, d) -> (E, C, d), per-expert gated MLP.

    ``plans``: the layer's plan subtree — (E,)-stacked GroupPlans per
    up/gate/down projection, vmapped alongside the stacked expert params.
    """
    fn = ACTIVATIONS[act]
    if _grouped(p, flgw):
        def one(pu, pg, pd, x, pl):
            up = proj(pu, x, flgw, plan=plan_of(pl, "up"))
            up = fn(proj(pg, x, flgw, plan=plan_of(pl, "gate"))) * up
            return proj(pd, up, flgw, plan=plan_of(pl, "down"))
        if plans:
            return jax.vmap(one)(p["up"], p["gate"], p["down"], xe, plans)
        return jax.vmap(lambda pu, pg, pd, x: one(pu, pg, pd, x, None))(
            p["up"], p["gate"], p["down"], xe)
    up = jnp.einsum("ecd,edf->ecf", xe, p["up"]["w"])
    gate = fn(jnp.einsum("ecd,edf->ecf", xe, p["gate"]["w"]))
    return jnp.einsum("ecf,efd->ecd", up * gate, p["down"]["w"])


def _grouped(p, flgw) -> bool:
    return flgw is not None and flgw.enabled and "ig" in p["up"]


def _balance(probs, gate_e, cfg, b: int, s: int):
    """Balance loss over all ``n_experts``. ``switch``: E * sum_i of the
    mean score times the top-1 share (Switch Transformer). ``seq``:
    DeepSeek's sequence-wise form, per sequence sum_i f_i P_i with f_i
    the share of the sequence's top-k picks of expert i times E / k and
    P_i its mean score, averaged over sequences (unweighted; the train
    loss multiplies by ``aux_weight``)."""
    e, k = cfg.n_experts, cfg.top_k
    if cfg.aux_form == "switch":
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(gate_e[:, 0], e, dtype=jnp.float32),
                      axis=0)
        return e * jnp.sum(me * ce)
    counts = jnp.sum(jax.nn.one_hot(gate_e.reshape(b, s * k), e,
                                    dtype=jnp.float32), axis=1)   # (b, e)
    f = counts * (e / (k * s))
    pm = jnp.mean(probs.reshape(b, s, e), axis=1)
    return jnp.mean(jnp.sum(f * pm, axis=-1))


# Tokens a dropless pass dispatches at once: its buffers hold chunk x top_k
# assignments, the worst case, whatever share of them the held experts get.
DROPLESS_CHUNK = 4096


def _held_dropless(p, xf, gate_w, gate_e, cfg):
    """Sum over the held experts among each token's top-k of its weight
    times the expert's SwiGLU, every assignment kept. Returns (out, stats):
    the assignments routed to held experts and the largest held expert's
    load over their mean.

    Longer inputs go in chunks of ``DROPLESS_CHUNK`` tokens, each
    rematerialised in the backward, so the worst-case buffers stay those of
    one chunk."""
    t, d = xf.shape
    c = DROPLESS_CHUNK
    if t <= c or t % c:
        out, sizes = _dropless_chunk(p, xf, gate_w, gate_e, cfg)
    else:
        n, k = t // c, cfg.top_k
        out, sizes = jax.lax.map(
            jax.checkpoint(lambda a: _dropless_chunk(p, *a, cfg)),
            (xf.reshape(n, c, d), gate_w.reshape(n, c, k),
             gate_e.reshape(n, c, k)))
        out, sizes = out.reshape(t, d), jnp.sum(sizes, axis=0)
    routed = jnp.sum(sizes).astype(jnp.float32)
    peak = (jnp.max(sizes).astype(jnp.float32) * cfg.n_held
            / jnp.maximum(routed, 1))
    return out, {"routed": routed, "peak": peak}


def _dropless_chunk(p, xf, gate_w, gate_e, cfg):
    """One chunk of ``_held_dropless``; returns (out, assignments per held
    expert)."""
    t, d = xf.shape
    k, held = cfg.top_k, cfg.n_held
    local = gate_e - cfg.expert_first
    mine = (local >= 0) & (local < held)
    flat = jnp.where(mine, local, held).reshape(-1)         # absent: last
    order = jnp.argsort(flat, stable=True)                  # (t*k,)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
    # the TPU's ragged_dot writes only the rows its groups cover: rows past
    # the held assignments keep whatever the buffer held, in its output
    # and in its input's gradient, so both are masked here
    valid = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(valid, jnp.repeat(xf, k, axis=0).at[order].get(
        unique_indices=True), 0)
    fn = ACTIVATIONS[cfg.mlp_act]
    with jax.named_scope(scopes.EXPERTS):
        up = jax.lax.ragged_dot(xs, p["up"]["w"], sizes)
        gate = fn(jax.lax.ragged_dot(xs, p["gate"]["w"], sizes))
        ys = jnp.where(valid, jax.lax.ragged_dot(up * gate, p["down"]["w"],
                                                 sizes), 0)
    # undo the sort and weigh each token's k assignments, accumulating in
    # f32
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
    y = ys.at[inv].get(unique_indices=True).reshape(t, k, d)
    w = jnp.where(mine, gate_w, 0.0)
    out = jnp.einsum("tkd,tk->td", y, w.astype(y.dtype),
                     preferred_element_type=jnp.float32).astype(xf.dtype)
    return out, sizes


def moe(p, x, cfg, *, flgw: Optional[FLGWConfig] = None,
        dropless: bool = False, plans=None):
    """x: (B, S, d) -> (B, S, d). Returns (out, aux_loss, stats).

    ``plans``: this MoE layer's entry of a cached PlanState (per-expert
    stacked GroupPlans; None falls back to per-call re-encoding).

    ``dropless=True`` keeps every assignment — used on the decode path,
    where a dropped token would silently corrupt that sequence's
    cache/state forever; ``capacity_factor`` 0 makes training dropless
    too. ``stats`` holds the dropless dispatch's counters ({} otherwise).
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(t, d)

    with jax.named_scope(scopes.MOE_ROUTE):
        logits = (xf.astype(jnp.float32) @ p["router"])        # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_e = jax.lax.top_k(probs, k)               # (T, k)
        if cfg.moe_renorm:
            gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
        aux = _balance(probs, gate_e, cfg, b, s)
        dropless = dropless or cfg.capacity_factor <= 0
        if dropless and not _grouped(p, flgw):
            out, stats = _held_dropless(p, xf, gate_w, gate_e, cfg)
        else:
            out, stats = _capacity(p, xf, gate_w, gate_e, cfg, flgw, plans,
                                   dropless), {}
    out = out.reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope(scopes.SHARED_EXPERTS):
            out = out + mlp(p["shared"], x, act=cfg.mlp_act)
    return out, aux, stats


def _capacity(p, xf, gate_w, gate_e, cfg, flgw, plans, dropless):
    """Capacity-bounded dispatch over all experts (every expert held):
    per-expert capacity ``t*k/E * capacity_factor``, or the worst case t*k
    when ``dropless``; drops are the standard trade of training."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    if cfg.n_held != e:
        raise ValueError("the capacity-bounded dispatch computes every "
                         "expert; a held share needs capacity_factor 0")
    if dropless:
        cap = t * k
    else:
        cap = int(max(1, round(t * k / e * cfg.capacity_factor)))
        cap = min(cap, t)

    # Sort (token, slot) assignments by expert; first `cap` per expert kept.
    flat_e = gate_e.reshape(-1)                                # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # position of each sorted entry within its expert run
    pos_in_e = jnp.arange(t * k) - jnp.searchsorted(se, se, side="left")
    keep = pos_in_e < cap
    slot = jnp.where(keep, se * cap + pos_in_e, e * cap)       # overflow -> drop

    tok_of_slot = jnp.full((e * cap + 1,), t, jnp.int32).at[slot].set(
        st, mode="drop")[:-1]                                  # (E*C,)
    w_of_slot = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(
        sw, mode="drop")[:-1]

    xe = jnp.take(xf, jnp.minimum(tok_of_slot, t - 1), axis=0)
    xe = jnp.where((tok_of_slot < t)[:, None], xe, 0).reshape(e, cap, d)
    with jax.named_scope(scopes.EXPERTS):
        ye = _expert_ffn(p, xe, flgw, plans, cfg.mlp_act).reshape(e * cap, d)
    ye = ye * w_of_slot[:, None].astype(ye.dtype)

    return (jnp.zeros((t + 1, d), xf.dtype)
            .at[tok_of_slot].add(ye, mode="drop")[:-1])
