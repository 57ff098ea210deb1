"""Unified model configuration covering the 10 assigned architectures.

Layer heterogeneity (local/global attention, MoE cadence, Mamba/attention
interleave) is expressed as a repeating *pattern* of length ``period``; the
stack is compiled as ``lax.scan`` over ``n_layers // period`` macro-blocks
with the ``period`` slots unrolled inside the body — small HLO, fast compile,
exact per-layer types.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SlotSpec:
    """Static type of one layer slot inside the repeating pattern."""
    mixer: str = "attn"          # "attn" | "mla" | "ssm"
    window: int = 0              # 0 = global attention; >0 = sliding window
    ffn: str = "mlp"             # "mlp" | "moe" | "moe_dense" (residual MoE)
    cross: bool = False          # add cross-attention (decoder of enc-dec)
    causal: bool = True          # False for encoder (bidirectional) stacks


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | vlm | audio | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    pattern: Tuple[SlotSpec, ...] = (SlotSpec(),)
    # slots applied once, before the scanned pattern (DeepSeek's
    # ``first_k_dense_replace`` leading dense layers); counted in n_layers
    lead: Tuple[SlotSpec, ...] = ()
    # attention details
    logit_softcap: float = 0.0   # final-logit softcap (gemma2)
    attn_softcap: float = 0.0    # attention-logit softcap (gemma2)
    rope_theta: float = 10000.0
    # YaRN RoPE scaling (arXiv:2309.00071); factor 1 is plain RoPE
    yarn_factor: float = 1.0
    yarn_original_max: int = 4096
    yarn_mscale_all_dim: float = 0.0
    # multi-head latent attention (mixer "mla", DeepSeek-V2 §2.1)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25   # 0: dropless (every assignment kept)
    shared_d_ff: int = 0         # width of the shared experts' SwiGLU (0: none)
    moe_renorm: bool = True      # top-k weights renormalised to sum to 1
    aux_form: str = "switch"     # switch (top-1 share) | seq (per sequence)
    aux_weight: float = 0.01     # balance loss weight in the train loss
    experts_held: int = 0        # routed experts held here (0: all)
    expert_first: int = 0        # id of the first held expert
    # ssm (mamba2 / jamba)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256         # SSD chunk length (perf knob)
    # enc-dec (whisper)
    encoder_layers: int = 0
    num_frames: int = 0          # audio-stub source positions
    # vlm (paligemma)
    prefix_len: int = 0          # image-patch prefix length (stub embeddings)
    # activation / norm
    gated_mlp: bool = True
    mlp_act: str = "gelu_tanh"   # gelu_tanh | silu
    embed_scale: bool = True     # embeddings times sqrt(d_model) (Gemma)
    norm_plus_one: bool = True   # RMSNorm weight 1 + w, w from 0 (Gemma); else w from 1
    norm_eps: float = 1e-6
    tie_embeddings: bool = True  # False: a separate (vocab, d) output head
    dtype: Any = jnp.bfloat16
    # FLGW (the paper's technique)
    flgw_groups: int = 1
    flgw_path: str = "masked"    # dense | masked | grouped
    flgw_targets: Tuple[str, ...] = ("mlp",)   # mlp | attn | moe
    # training
    remat: bool = True
    use_flash: bool = False     # fused Pallas attention core (perf path)

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_blocks(self) -> int:
        scanned = self.n_layers - len(self.lead)
        assert scanned % self.period == 0, \
            f"{self.name}: {scanned} % {self.period} != 0"
        return scanned // self.period

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def flgw_on(self, target: str) -> bool:
        return self.flgw_groups > 1 and self.flgw_path != "dense" \
            and target in self.flgw_targets

    def with_updates(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def _count(cfg: ModelConfig, experts_per_moe: int) -> int:
    """Parameter count with MoE slots counted as ``experts_per_moe`` FFNs."""
    d, h = cfg.d_model, cfg.head_dim
    total = cfg.vocab * d                              # embeddings
    if not cfg.tie_embeddings:
        total += cfg.vocab * d

    def attn_params():
        return d * h * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * h * d

    def mla_params():
        hq, r = cfg.n_heads, cfg.kv_lora_rank
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        return (d * hq * qk + d * (r + cfg.qk_rope_dim) + r
                + r * hq * (cfg.qk_nope_dim + cfg.v_head_dim)
                + hq * cfg.v_head_dim * d)

    def mlp_params(ff):
        return d * ff * (3 if cfg.gated_mlp else 2)

    def ssm_params():
        di, ns = cfg.d_inner, cfg.ssm_state
        # in_proj (x, z, B, C, dt), conv, out_proj, A/D/dt_bias
        return (d * (2 * di + 2 * ns + cfg.ssm_heads)
                + cfg.conv_width * (di + 2 * ns) + di * d + 3 * cfg.ssm_heads)

    def slot_params(slot):
        n = {"attn": attn_params, "mla": mla_params}.get(
            slot.mixer, ssm_params)()
        if slot.cross:
            n += attn_params()
        if slot.ffn == "none":
            pass
        elif slot.ffn == "mlp":
            n += mlp_params(cfg.d_ff)
        else:  # moe | moe_dense
            n += experts_per_moe * mlp_params(cfg.moe_d_ff or cfg.d_ff)
            n += d * cfg.n_experts                     # router
            n += mlp_params(cfg.shared_d_ff) if cfg.shared_d_ff else 0
            if slot.ffn == "moe_dense":
                n += mlp_params(cfg.d_ff)              # dense residual branch
        return n + 4 * d                               # norms (approx)

    total += sum(slot_params(s) for s in cfg.lead)
    total += cfg.n_blocks * sum(slot_params(s) for s in cfg.pattern)
    if cfg.encoder_layers:
        total += cfg.encoder_layers * (attn_params() + mlp_params(cfg.d_ff)
                                       + 4 * d)
    return int(total)


def param_count(cfg: ModelConfig) -> int:
    """Total parameters held (for 6·N·D model-FLOPs of dense models)."""
    return _count(cfg, cfg.n_held)


def active_param_count(cfg: ModelConfig) -> int:
    """Active parameters per token (MoE: only top_k experts fire)."""
    return _count(cfg, cfg.top_k if cfg.n_experts else 0)
