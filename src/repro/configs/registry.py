"""Architecture registry: ``--arch <id>`` lookup + input shape cells.

Every assigned architecture registers its exact published config, a reduced
smoke config (same family, tiny dims) and its shape-cell applicability.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}
_SMOKE: Dict[str, Callable[[], ModelConfig]] = {}

ARCH_IDS = (
    "gemma2_2b", "gemma2_27b", "gemma3_12b", "internlm2_20b",
    "paligemma_3b", "mixtral_8x22b", "arctic_480b", "whisper_large_v3",
    "jamba_1_5_large", "mamba2_1_3b", "deepseek_v2_lite", "ic3net",
)

# Shape cells (assignment): name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k":    (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k":  (32_768, 128, "decode"),
    "long_500k":   (524_288, 1, "decode"),
}

# long_500k policy (DESIGN.md §6): sub-quadratic / bounded-KV archs only.
LONG_OK = {"mamba2_1_3b", "jamba_1_5_large", "mixtral_8x22b", "gemma3_12b"}
# ic3net is the paper's own network: MARL shapes only (no LM shape cells).
NO_LM_SHAPES = {"ic3net"}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def register_smoke(name: str):
    def deco(fn):
        _SMOKE[name] = fn
        return fn
    return deco


def _load(name: str):
    if name not in _REGISTRY:
        importlib.import_module(f"repro.configs.{name}")


def get_config(name: str, **overrides) -> ModelConfig:
    _load(name)
    cfg = _REGISTRY[name]()
    return cfg.with_updates(**overrides) if overrides else cfg


def get_smoke_config(name: str, **overrides) -> ModelConfig:
    _load(name)
    cfg = _SMOKE[name]()
    return cfg.with_updates(**overrides) if overrides else cfg


def cells(arch: str) -> list[str]:
    """Shape cells applicable to this arch (skips documented in DESIGN.md)."""
    if arch in NO_LM_SHAPES:
        return []
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if arch in LONG_OK:
        out.append("long_500k")
    return out


def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in ARCH_IDS for s in cells(a)]


def input_specs(cfg: ModelConfig, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of one cell.

    No device allocation — the dry-run lowers against these directly.
    """
    seq, batch, kind = SHAPES[shape_name]
    i32 = jnp.int32
    if kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
            "targets": jax.ShapeDtypeStruct((batch, seq), i32),
            "positions": jax.ShapeDtypeStruct((batch, seq), i32),
        }
        if cfg.prefix_len:  # vlm stub: precomputed patch embeddings
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (batch, cfg.prefix_len, cfg.d_model), cfg.dtype)
        if cfg.encoder_layers:  # audio stub: precomputed frame embeddings
            specs["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.num_frames, cfg.d_model), cfg.dtype)
        return specs
    if kind == "prefill":
        specs = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
            "positions": jax.ShapeDtypeStruct((batch, seq), i32),
        }
        if cfg.prefix_len:
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (batch, cfg.prefix_len, cfg.d_model), cfg.dtype)
        if cfg.encoder_layers:
            specs["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.num_frames, cfg.d_model), cfg.dtype)
        return specs
    # decode: one new token against a seq-length cache (built separately)
    return {
        "tokens": jax.ShapeDtypeStruct((batch, 1), i32),
        "positions": jax.ShapeDtypeStruct((batch, 1), i32),
    }
