"""Pure-jnp oracle for the flash attention kernel.

Semantics shared with the kernel: GQA (q heads grouped onto kv heads),
causal and/or sliding-window masking by absolute positions starting at 0,
optional gemma-style attention-logit softcap, f32 softmax, output in the
query dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def ref_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0,
                  softcap: float = 0.0, scale: float | None = None
                  ) -> jax.Array:
    """q/k: (B, Hq|Hkv, S|T, D); v: (B, Hkv, T, Dv). Returns
    (B, Hq, S, Dv)."""
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    qpk = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, qpk, s, d)
    logits = jnp.einsum("bgqsd,bgtd->bgqst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    allowed = jnp.ones((s, t), bool)
    if causal:
        allowed &= kpos <= qpos
    if window > 0:
        allowed &= kpos > qpos - window
    logits = jnp.where(allowed, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgqst,bgtd->bgqsd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, s, v.shape[-1]).astype(q.dtype)
