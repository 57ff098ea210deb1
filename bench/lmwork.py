"""Operations a language-model training step needs, from shapes and the
step's own count of routed assignments.

What the mathematics requires, not what one implementation computes: the
forward once and the backward twice the forward, no rematerialised
recompute; causal attention by its half (query i against keys 0..i); the
routed experts by the assignments the router sent to the experts held
here, which the step counts (``moe_routed``), not by a capacity.
"""
from __future__ import annotations

TRAIN = 3  # forward + backward (2x forward)


def step_work(c: dict, t: dict) -> dict:
    """Operations of one step apart from the routed experts (``step``),
    those of one routed assignment (``per_assignment``), and of the
    attention core alone (``attn_core``), forward and backward."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, r = c["v_head_dim"], c["kv_lora_rank"]
    s, b = t["seq_len"], t["batch"]
    tokens = s * b
    layers = c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    moe = layers - dense
    mla_proj = 2 * tokens * (d * h * (dn + dr) + d * (r + dr)
                             + r * h * (dn + dv) + h * dv * d)
    core = 2 * b * h * (s * (s + 1) // 2) * ((dn + dr) + dv)
    mlp = 2 * tokens * 3 * d * c["intermediate_size"]
    shared = 2 * tokens * 3 * d * (c["moe_intermediate_size"]
                                   * c["n_shared_experts"])
    router = 2 * tokens * d * c["router_experts"]
    head = 2 * tokens * d * c["vocab_size"]
    fwd = (layers * (mla_proj + core) + dense * mlp
           + moe * (shared + router) + head)
    return {"step": TRAIN * fwd,
            "per_assignment": TRAIN * 2 * 3 * d * c["moe_intermediate_size"],
            "attn_core": TRAIN * layers * core,
            "mla": TRAIN * layers * (mla_proj + core),
            "moe": TRAIN * moe * (shared + router)}


def routed_work(work: dict, routed_per_layer) -> float:
    """Operations of the routed experts for one step's counts."""
    return work["per_assignment"] * sum(routed_per_layer)
