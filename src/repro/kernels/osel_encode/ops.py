"""Jit'd wrapper for the OSEL encode kernel."""
from __future__ import annotations

import jax

from repro.kernels import default_interpret
from repro.kernels.osel_encode.osel_encode import encode_mask
from repro.kernels.osel_encode import ref as _ref


def osel_mask(ig_idx: jax.Array, og_idx: jax.Array,
              interpret: bool | None = None) -> jax.Array:
    """OSEL mask (uint8) from the grouping index vectors."""
    if interpret is None:
        interpret = default_interpret()
    return encode_mask(ig_idx, og_idx, interpret=interpret)


def reference_mask(ig: jax.Array, og: jax.Array) -> jax.Array:
    """Baseline IS @ OS mask (bool) from raw grouping matrices."""
    return _ref.ref_mask_matmul(ig, og)
