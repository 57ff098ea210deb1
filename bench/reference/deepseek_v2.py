"""Plain reference of DeepSeek-V2 training on one chip's share of the
routed experts, in float32.

Follows arXiv:2405.04434: multi-head latent attention (§2.1) with YaRN
RoPE, a leading dense SwiGLU layer, then DeepSeekMoE layers (§2.2): a
softmax router over every routed expert, the greedy top-k without
renormalisation times the routed scale, the held experts' SwiGLUs weighed
by their scores, the shared experts' SwiGLU on every token, and the
sequence-wise balance loss alpha_1 * sum_i f_i P_i. Next-token
cross-entropy over the vocabulary slice, AdamW with a global-norm clip.

The weights are one nested dict of float32 arrays, which ``init_params``
makes from a key: ``embed/embedding``, ``head/w``, ``final_norm``,
``lead/slot0/...`` for the dense layer and ``blocks/slot0/...`` for the
MoE layers, stacked on a leading layer axis. Nothing is imported from
the program. Each sequence is computed on its own, each layer
rematerialised, and attention in query blocks, so the reference fits in
one chip's memory; the batch gradient is the sum of the sequences'.

Departure, also made by the program: RoPE pairs the columns (i, i + D/2)
of the rope part, where DeepSeek's checkpoints pair (2i, 2i + 1).

Every matrix product goes through ``product``: float32 at the highest
precision, or, for the control, with both inputs rounded to float8 e4m3.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def product(kind: str):
    """``mm(spec, a, b)``: an einsum in float32 at the highest precision;
    ``float8`` rounds both inputs to float8 e4m3 first."""
    def rnd(x):
        if kind == "float8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b),
                          precision=jax.lax.Precision.HIGHEST)
    return mm


# --- weights -------------------------------------------------------------

def init_params(key, c) -> dict:
    """Initial weights from ``key`` by the configuration file's ``assumed``
    init: every projection, the router and the output head N(0, 1/fan_in),
    the embedding N(0, 0.02^2), RMSNorm weights 1. Float32, in the layout
    of the module docstring; a projection's matrix is (in, out) and the
    layers of a kind are stacked on a leading axis."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    held, f = c["n_routed_experts"], c["moe_intermediate_size"]
    n_lead = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_lead
    keys = iter(jax.random.split(key, 32))

    def w(*shape):                  # fan_in is the second-last axis
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * shape[-2] ** -0.5)

    def norm(*shape):
        return {"scale": jnp.ones(shape, jnp.float32)}

    def swiglu(n, width, *experts):
        return {"up": {"w": w(n, *experts, d, width)},
                "gate": {"w": w(n, *experts, d, width)},
                "down": {"w": w(n, *experts, width, d)}}

    def layer(n):
        return {"mixer": {"q": {"w": w(n, d, h * (dn + dr))},
                          "kv_a": {"w": w(n, d, r + dr)},
                          "kv_norm": norm(n, r),
                          "kv_b": {"w": w(n, r, h * (dn + dv))},
                          "o": {"w": w(n, h * dv, d)}},
                "norm1": norm(n, d), "norm2": norm(n, d)}

    v = c["vocab_size"]
    params = {
        "embed": {"embedding": 0.02 * jax.random.normal(
            next(keys), (v, d), jnp.float32)},
        "head": {"w": jax.random.normal(next(keys), (v, d), jnp.float32)
                 * d ** -0.5},
        "final_norm": norm(d),
        "lead": {"slot0": dict(layer(n_lead), ffn=swiglu(
            n_lead, c["intermediate_size"]))},
        "blocks": {"slot0": dict(layer(n_moe), moe=dict(
            swiglu(n_moe, f, held), router=w(n_moe, d, c["router_experts"]),
            shared=swiglu(n_moe, f * c["n_shared_experts"])))},
    }
    return params


# --- positions -----------------------------------------------------------

def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(c) -> np.ndarray:
    """YaRN frequencies of the rope columns: ``theta^(-2i/D)`` where the
    dimension turns more than ``beta_fast`` times over the original
    context, that over ``factor`` where it turns fewer than ``beta_slow``
    times, and a linear ramp between."""
    y, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / y["factor"] * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(c) -> float:
    y = c["rope_scaling"]
    m = _mscale(y["factor"], y["mscale_all_dim"])
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, inv_freq):
    """x: (S, H, D); rotate (i, i + D/2) by position * inv_freq[i]."""
    s, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# --- layers --------------------------------------------------------------

def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(q, k, v, scale, mm):
    """Causal softmax attention of one sequence, a block of queries at a
    time against the keys up to the block's end. q, k: (S, H, Dqk);
    v: (S, H, Dv)."""
    s = q.shape[0]
    block = min(Q_BLOCK, s)
    outs = []
    for lo in range(0, s, block):
        hi = lo + block
        z = mm("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
        allowed = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(allowed, z, -jnp.inf), axis=-1)
        outs.append(mm("hqk,khd->qhd", p, v[:hi]))
    return jnp.concatenate(outs, axis=0)


def mla(p, x, c, mm):
    s = x.shape[0]
    h, dn = c["num_attention_heads"], c["qk_nope_head_dim"]
    dr, dv, r = c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    inv = jnp.asarray(yarn_inv_freq(c))
    q = mm("sd,de->se", x, p["q"]["w"]).reshape(s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv)], axis=-1)
    kv_a = mm("sd,de->se", x, p["kv_a"]["w"])
    lat = rms(kv_a[:, :r], p["kv_norm"]["scale"], c["rms_norm_eps"])
    k_pe = rope(kv_a[:, None, r:], inv)                      # (s, 1, dr)
    kv = mm("sr,re->se", lat, p["kv_b"]["w"]).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (s, h, dr))],
                        axis=-1)
    out = attention(q, k, kv[..., dn:], softmax_scale(c), mm)
    return mm("se,ed->sd", out.reshape(s, h * dv), p["o"]["w"])


def swiglu(p, x, mm):
    a = jax.nn.silu(mm("sd,df->sf", x, p["gate"]["w"]))
    return mm("sf,fd->sd", a * mm("sd,df->sf", x, p["up"]["w"]),
              p["down"]["w"])


def moe(p, x, c, mm):
    """(out, balance): the held experts' part of the routed result plus
    the shared experts, and sum_i f_i P_i of this sequence."""
    s = x.shape[0]
    e, k = c["router_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("sd,de->se", x, p["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * c["routed_scaling_factor"]
    chosen = jax.nn.one_hot(top_e, e)                         # (s, k, e)
    weight = jnp.einsum("ske,sk->se", chosen, top_w)          # 0 if not picked
    held = p["up"]["w"].shape[0]
    coef = weight[:, c["expert_first"]:c["expert_first"] + held]
    a = jax.nn.silu(mm("sd,edf->esf", x, p["gate"]["w"]))
    y = mm("esf,efd->esd", a * mm("sd,edf->esf", x, p["up"]["w"]),
           p["down"]["w"])
    out = jnp.einsum("esd,se->sd", y, coef,
                     precision=jax.lax.Precision.HIGHEST)
    out = out + swiglu(p["shared"], x, mm)
    f = jnp.sum(chosen, axis=(0, 1)) * e / (k * s)
    return out, jnp.sum(f * jnp.mean(probs, axis=0))


def layer(p, x, c, mm, kind):
    eps = c["rms_norm_eps"]
    x = x + mla(p["mixer"], rms(x, p["norm1"]["scale"], eps), c, mm)
    hn = rms(x, p["norm2"]["scale"], eps)
    if kind == "dense":
        return x + swiglu(p["ffn"], hn, mm), jnp.zeros(())
    out, bal = moe(p["moe"], hn, c, mm)
    return x + out, bal


def logits(params, tokens, c, mm):
    """(logits over the vocabulary slice, the balance loss summed over the
    MoE layers) of one sequence."""
    x = params["embed"]["embedding"][tokens]
    bal = jnp.zeros(())
    stacks = [("dense", params["lead"]["slot0"]),
              ("moe", params["blocks"]["slot0"])]
    for kind, stacked in stacks:
        step = jax.checkpoint(lambda p, x, kind=kind: layer(p, x, c, mm, kind))
        for i in range(jax.tree.leaves(stacked)[0].shape[0]):
            x, b = step(jax.tree.map(lambda a: a[i], stacked), x)
            bal = bal + b
    x = rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return mm("sd,vd->sv", x, params["head"]["w"]), bal


def sequence_loss(params, tokens, targets, c, mm):
    """(sum of the sequence's token NLLs, its balance loss summed over the
    MoE layers)."""
    z, bal = logits(params, tokens, c, mm)
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, targets[:, None], -1)[:, 0]
    return jnp.sum(nll), bal


def _grad_fn(c, kind: str, n_seq: int, n_tok: int):
    mm = product(kind)
    alpha = c["aux_loss_alpha"]

    def loss(params, tokens, targets):
        nll, bal = sequence_loss(params, tokens, targets, c, mm)
        return nll / n_tok + alpha * bal / n_seq

    def acc(g, params, tokens, targets):
        v, d = jax.value_and_grad(loss)(params, tokens, targets)
        return jax.tree.map(jnp.add, g[0], d), g[1] + v
    return jax.jit(acc, donate_argnums=0)


@jax.jit
def _clip(grads, limit):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, limit / (norm + 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def _adamw(c):
    b1, b2, wd, lr = c["adam_b1"], c["adam_b2"], c["weight_decay"], c["lr"]

    def upd(params, grads, mu, nu, count):
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + 1e-8)
            - lr * wd * p, params, mu, nu)
        return params, mu, nu
    return jax.jit(upd, donate_argnums=(0, 1, 2, 3))


def train(params, batches, c, *, product_kind: str = "highest",
          keep: float = 1.0):
    """AdamW steps from ``params`` (float32 on the device), one a batch.
    ``batches``: (tokens, targets) int32 arrays of (B, S). ``keep`` < 1
    trains on that share of each batch's sequences (a planted fault).
    Returns (losses, the norm of each leaf of the first step's clipped
    gradient, final params). The optimizer moments wait on the host while
    gradients are taken."""
    first, losses = None, []
    mu = nu = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    upd = _adamw(c)
    n_seq = max(1, int(batches[0][0].shape[0] * keep))
    grad = _grad_fn(c, product_kind, n_seq, n_seq * batches[0][0].shape[1])
    for step, (tokens, targets) in enumerate(batches, start=1):
        g = (jax.tree.map(jnp.zeros_like, params), jnp.zeros(()))
        for b in range(n_seq):
            g = grad(g, params, tokens[b], targets[b])
        grads, loss = g
        grads = _clip(grads, c["clip"])
        losses.append(float(loss))
        if first is None:
            first = jax.tree.map(float, jax.jit(lambda t: jax.tree.map(
                jnp.linalg.norm, t))(grads))
        params, mu, nu = upd(params, grads, *jax.device_put((mu, nu)),
                             float(step))
        mu, nu = jax.device_get((mu, nu))
    return losses, first, params
