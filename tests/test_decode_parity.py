"""Decode-vs-prefill parity: the strongest integration test in the repo.

Token-by-token decoding through the (ring-buffer) KV / SSM caches must
reproduce the cache-free full-sequence forward — including sliding-window
layers whose cache is shorter than the stream (the ring buffer wraps).

The grouped-serving section checks the plan-amortization contract: decode
against the PlanState cached beside the KV/SSM caches must be *bitwise*
equal to the plan=None per-call re-encoding path, for attention, SSM and
MoE FLGW targets alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import encoder
from repro.models import transformer
from repro.models.config import ModelConfig, SlotSpec


def _full_then_decode(cfg, seq, key=0, atol=2e-2):
    k = jax.random.PRNGKey(key)
    params, _ = transformer.lm_init(k, cfg)
    b = 2
    tokens = jax.random.randint(jax.random.fold_in(k, 1), (b, seq), 0,
                                cfg.vocab, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (b, seq))

    # serving-semantics reference: dropless MoE (decode is dropless too)
    full_logits, _, _ = transformer.lm_apply(params, cfg, tokens, positions,
                                             remat=False, moe_dropless=True)

    cache = transformer.init_cache(cfg, b, seq)
    step_logits = []
    apply = jax.jit(lambda p, t, pos, c: transformer.lm_apply(
        p, cfg, t, pos, cache=c, remat=False))
    for t in range(seq):
        lg, _, cache = apply(params, tokens[:, t:t + 1],
                             positions[:, t:t + 1], cache)
        step_logits.append(lg[:, 0])
    decode_logits = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(
        np.asarray(decode_logits, jnp.float32),
        np.asarray(full_logits, jnp.float32), rtol=2e-2, atol=atol)


def test_parity_global_attention():
    cfg = registry.get_smoke_config("internlm2_20b")
    _full_then_decode(cfg, seq=12)


def test_parity_latent_attention_and_held_experts():
    """DeepSeek-V2-Lite's smoke: MLA decodes from its cached latent and
    rotated key, through the leading dense layer and the dropless MoE."""
    cfg = registry.get_smoke_config("deepseek_v2_lite")
    _full_then_decode(cfg, seq=12)


def test_parity_sliding_window_ring_buffer_wraps():
    """seq > window: the ring buffer must overwrite old positions and the
    decode output must still match the windowed full forward."""
    cfg = ModelConfig(
        name="swa_test", family="dense", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab=128,
        pattern=(SlotSpec(mixer="attn", window=4, ffn="mlp"),), remat=False)
    # cache length = window (4) < seq (12): three full wraps
    _full_then_decode(cfg, seq=12)


def test_parity_alternating_local_global():
    cfg = registry.get_smoke_config("gemma2_2b")   # window 16 slots
    _full_then_decode(cfg, seq=24)                 # exceeds local window


def test_parity_ssm_decode():
    cfg = registry.get_smoke_config("mamba2_1_3b")
    _full_then_decode(cfg, seq=10, atol=5e-2)


@pytest.mark.slow
def test_parity_hybrid_jamba():
    cfg = registry.get_smoke_config("jamba_1_5_large")
    _full_then_decode(cfg, seq=8, atol=5e-2)


def test_parity_moe_decode():
    cfg = registry.get_smoke_config("mixtral_8x22b")
    _full_then_decode(cfg, seq=8, atol=5e-2)


# ---------------------------------------------------------------------------
# Grouped serving: cached PlanState vs per-call re-encoding (bitwise)
# ---------------------------------------------------------------------------

def _grouped_serve_bitwise(cfg, seq):
    """Prefill (prompt replay, as examples/serve.py) + decode twice — once
    with the PlanState beside the KV cache, once plan-less — and demand
    bitwise-identical logits at every step."""
    k = jax.random.PRNGKey(3)
    params, _ = transformer.lm_init(k, cfg)
    b = 1
    tokens = jax.random.randint(jax.random.fold_in(k, 1), (b, seq), 0,
                                cfg.vocab, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (b, seq))
    apply = jax.jit(lambda p, t, pos, c: transformer.lm_apply(
        p, cfg, t, pos, cache=c, remat=False))

    runs = {}
    for cached in (True, False):
        cache = transformer.init_cache(cfg, b, seq,
                                       params=params if cached else None)
        assert isinstance(cache["plans"],
                          encoder.PlanState if cached else tuple)
        logits = []
        for t in range(seq):                # prefill replay + decode steps
            lg, _, cache = apply(params, tokens[:, t:t + 1],
                                 positions[:, t:t + 1], cache)
            logits.append(np.asarray(lg[:, 0]))  # noqa: ANL002 — parity test materializes every step deliberately
        runs[cached] = np.stack(logits, axis=1)
        if cached:                          # plans ride the cache unchanged
            assert isinstance(cache["plans"], encoder.PlanState)
    np.testing.assert_array_equal(runs[True], runs[False])


def _grouped(**kw):
    base = dict(flgw_groups=4, flgw_path="grouped", dtype=jnp.float32,
                remat=False, vocab=64, d_model=32, n_heads=2, n_kv_heads=2,
                head_dim=16, d_ff=64, n_layers=2)
    base.update(kw)
    return ModelConfig(**base)


def test_grouped_serve_parity_attention_slots():
    cfg = _grouped(name="g_attn", family="dense",
                   flgw_targets=("mlp", "attn"))
    _grouped_serve_bitwise(cfg, seq=6)


def test_grouped_serve_parity_ssm_slots():
    cfg = _grouped(name="g_ssm", family="ssm",
                   pattern=(SlotSpec(mixer="ssm", ffn="mlp"),),
                   ssm_state=8, ssm_head_dim=16,
                   flgw_targets=("ssm", "mlp"))
    _grouped_serve_bitwise(cfg, seq=5)


def test_grouped_serve_parity_moe_slots():
    cfg = _grouped(name="g_moe", family="moe",
                   pattern=(SlotSpec(mixer="attn", ffn="moe"),),
                   n_experts=2, top_k=2, moe_d_ff=32,
                   flgw_targets=("moe", "attn"))
    _grouped_serve_bitwise(cfg, seq=5)


def test_windowed_cache_is_bounded():
    """init_cache allocates min(max_seq, window) for SWA slots."""
    cfg = ModelConfig(
        name="swa", family="dense", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, vocab=64,
        pattern=(SlotSpec(mixer="attn", window=4, ffn="mlp"),
                 SlotSpec(mixer="attn", window=0, ffn="mlp")))
    cache = transformer.init_cache(cfg, 1, 1024)
    assert cache["blocks"]["slot0"]["k"].shape[2] == 4       # bounded
    assert cache["blocks"]["slot1"]["k"].shape[2] == 1024    # global
