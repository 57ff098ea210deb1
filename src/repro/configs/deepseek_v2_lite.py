"""deepseek-v2-lite [moe]: 27L d=2048, MLA with 16 heads (kv_lora_rank 512,
q/k heads 128 + 64 RoPE, v heads 128, no query compression), YaRN x40 over
a 4096 original context; layer 0 a dense SwiGLU of 10944, layers 1-26 MoE:
64 routed experts of 1408 (top-6 greedy over softmax scores, no
renormalisation, routed scale 1) beside 2 shared experts (one SwiGLU of
2816), sequence-wise balance loss; vocab 102400, untied head, plain RMSNorm.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]"""
from repro.configs.registry import register, register_smoke
from repro.models.config import ModelConfig, SlotSpec

_DENSE = SlotSpec(mixer="mla", ffn="mlp")
_MOE = SlotSpec(mixer="mla", ffn="moe")
# what DeepSeek-V2-Lite shares with its smoke configuration
_COMMON = dict(
    family="moe", lead=(_DENSE,), pattern=(_MOE,), capacity_factor=0.0,
    moe_renorm=False, aux_form="seq",
    # alpha_1 of the sequence-wise loss; config.json does not carry it
    aux_weight=0.001, mlp_act="silu", embed_scale=False, norm_plus_one=False,
    norm_eps=1e-6, tie_embeddings=False, rope_theta=10000.0,
    yarn_factor=40.0, yarn_mscale_all_dim=0.707, use_flash=True)


@register("deepseek_v2_lite")
def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek_v2_lite", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=10944, vocab=102_400, n_experts=64, top_k=6,
        moe_d_ff=1408, shared_d_ff=2816, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, yarn_original_max=4096, **_COMMON)


@register_smoke("deepseek_v2_lite")
def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek_v2_lite_smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab=512, n_experts=8, top_k=2,
        moe_d_ff=32, shared_d_ff=64, kv_lora_rank=32, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, yarn_original_max=16,
        **dict(_COMMON, use_flash=False))
