"""Jit'd public wrapper around the plan-encode (balanced-assign) kernel.

Pipeline (the TPU analogue of the FPGA's load-allocation unit):

  1. argmax    scores -> (pref, strength)   per-item group preference (VPU)
  2. Pallas    comparator-rank counting sort + prefix-sum placement
               (two tiled passes — see ``plan_encode.assign_slots``)
  3. scatter   slot_of_item -> (G, cap) buckets (inverse permutation, XLA)

Leading batch dims are folded into the kernel grid (stacked decoder layers
encode in one launch — no vmap-of-pallas needed). On non-TPU backends the
kernel runs in interpret mode; ``impl="reference"`` (or the shared
``repro.kernels.use_reference_impl`` switch, for GSPMD lowering) falls back
to the lexsort reference in ``ref.py``. There is no size cap: the placement
passes tile over ``(bi, bj)`` item pairs, so the VMEM working set is
independent of the item count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret, reference_impl_active
from repro.kernels.plan_encode import ref as _ref
from repro.kernels.plan_encode.plan_encode import assign_slots
# Placement-tile selection is shared with the static auditor
# (repro.kernels.plan_encode.audit) so the audited grid is, by
# construction, the grid this wrapper builds. Override per call
# (``balanced_assign(block=...)``) to force the multi-tile path on small
# inputs in tests.
from repro.kernels.tiling import plan_block as _plan_block
from repro.kernels.tiling import round_up as _round_up


def resolve_impl(items: int, impl: str | None = None) -> str:
    """Which implementation an ``items``-row encode will run — the single
    impl-selection policy, exposed so tests can assert on it.

    An **explicit** ``impl`` is binding. **Implicit** resolution
    (``impl=None``) prefers the kernel and falls back to the
    bitwise-identical lexsort reference only under the shared
    ``repro.kernels.use_reference_impl`` switch (intentional, silent —
    GSPMD cannot partition a Pallas custom call). Since the placement
    pass was tiled there is no size-based fallback: any ``items`` count
    runs the kernel, so ``items`` no longer affects the answer and is
    kept for call-site compatibility only.
    """
    if impl is not None:
        if impl not in ("pallas", "reference"):
            raise ValueError(
                f"impl must be 'pallas' or 'reference', got {impl!r}")
        return impl
    if reference_impl_active():
        return "reference"
    return "pallas"


@functools.partial(jax.jit, static_argnames=("axis", "slack", "interpret",
                                             "impl", "block"))
def _balanced_assign(scores: jax.Array, axis: int, slack: float,
                     interpret: bool | None, impl: str,
                     block: int | None) -> jax.Array:
    # The assignment is pure int metadata — no gradient ever flows through
    # it (the STE surrogate lives in grouped_apply's VJP). Cutting the
    # tangent here keeps jvp/grad of plan-deriving callers from trying to
    # differentiate the Pallas call.
    scores = jax.lax.stop_gradient(scores)
    if axis == 0:
        scores = jnp.swapaxes(scores, -1, -2)
    lead = scores.shape[:-2]
    m, g = scores.shape[-2:]
    cap = _ref.compute_cap(m, g, slack)
    if impl == "reference":
        f = functools.partial(_ref.ref_balanced_assign, slack=slack)
        for _ in lead:
            f = jax.vmap(f)
        return f(scores)
    if interpret is None:
        interpret = default_interpret()

    flat = scores.reshape((-1, m, g)) if lead else scores[None]
    length = flat.shape[0]
    pref = jnp.argmax(flat, axis=-1).astype(jnp.int32)       # (L, M)
    strength = jnp.max(flat, axis=-1).astype(jnp.float32)
    b = _plan_block(m, block)
    mp = _round_up(m, b)
    # Padding items: sentinel group g, -inf strength — never counted, never
    # placed (their garbage slots are sliced off below).
    pref = jnp.pad(pref, ((0, 0), (0, mp - m)), constant_values=g)
    strength = jnp.pad(strength, ((0, 0), (0, mp - m)),
                       constant_values=-jnp.inf)
    slot = assign_slots(pref[..., None], strength[..., None],
                        pref[:, None, :], strength[:, None, :],
                        g=g, cap=cap, bi=b, bj=b, interpret=interpret)
    slot = slot[:, :m, 0]                                    # (L, M)

    # Inverse permutation: bucket slot ids back to (G, cap) item lists.
    total = g * cap
    ids = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None],
                           (length, m))
    out = (jnp.full((length, total), m, jnp.int32)
           .at[jnp.arange(length)[:, None], slot].set(ids, mode="drop"))
    if lead:
        return out.reshape(*lead, g, cap)
    return out[0].reshape(g, cap)


def balanced_assign(scores: jax.Array, axis: int, slack: float = 1.0, *,
                    interpret: bool | None = None,
                    impl: str | None = None,
                    block: int | None = None) -> jax.Array:
    """Deal items into equal-capacity groups by argmax preference.

    ``scores``: (..., M, G) if axis==1 (rows of IG) or (..., G, N) if
    axis==0 (columns of OG); leading dims batch over stacked layers.
    Returns (..., G, cap) int32 item ids with ``cap = ceil(M/G · slack)``
    (padding slots hold M). Bitwise-identical to
    :func:`ref.ref_balanced_assign` for finite scores at any M — the
    placement passes tile, so there is no kernel size cap.

    ``block`` overrides the placement tile side (must stay a multiple of
    the 128-lane quantum for real-TPU layouts; tests force small tiles to
    drive the multi-tile path under interpret mode). Implementation
    selection (Pallas kernel vs lexsort reference) follows
    :func:`resolve_impl`.
    """
    items = scores.shape[-2] if axis else scores.shape[-1]
    impl = resolve_impl(items, impl)
    return _balanced_assign(scores, axis, slack, interpret, impl, block)


def reference(scores: jax.Array, axis: int, slack: float = 1.0) -> jax.Array:
    """The lexsort oracle (unbatched input)."""
    if axis == 0:
        scores = scores.T
    return _ref.ref_balanced_assign(scores, slack)
