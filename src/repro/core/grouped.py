"""Balanced group assignment + compact FLGW execution (custom VJP).

This module is the TPU adaptation of LearningGroup's *row-based load
balancing* (§III-C) and the accelerator's compact dataflow.

On the FPGA, rows are dealt evenly to C cores and the 1/G expected workload
makes the allocation converge. TPU SPMD needs *static shapes*, so we go one
step further: a **capacity-balanced assignment** gives every group exactly
``cap = ceil(M/G)`` row slots (and ``ceil(N/G)`` column slots). Rows are
sorted by their argmax group preference (ties broken by preference strength)
and dealt into group buckets in order; overflow rows of a popular group spill
into the next bucket. Deviation from the theoretical balanced workload is 0
by construction — the static-shape analogue of the paper's scheme (measured
against the paper's threshold/row-based schemes in benchmarks/table1).

``grouped_apply`` runs the compact path with a custom VJP:

  * dx, dW   — exact, via the transposed compact product (the paper's
               weight-transpose trick: swap IG/OG roles).
  * dIG, dOG — sparse-restricted straight-through gradient: the mask gradient
               is only known on surviving entries (that is all the backward
               pass computes — same restriction as the FPGA, which updates
               grouping matrices from the sparse errors it has on-chip).
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import default_interpret
from repro.kernels.flgw_matmul import ops as kops
from repro.kernels.plan_encode import ops as pe_ops
from repro.sharding.partition import constrain


class GroupPlan(NamedTuple):
    """Static-shape compact layout of one FLGW layer's mask.

    ``wc`` is the optional weight half of the encode output — the dense W
    compacted to ``(G, capM, capN)`` (:func:`attach_compact`), the paper's
    OSEL→core handoff. Plans used for *training* leave it ``None`` (W
    moves every step); serving attaches it once per params version so the
    consume path stops re-gathering W per call. Because ``wc`` caches
    *weight values* — unlike the int layout, which a plan signature
    certifies — it must always be (re-)derived from the params actually
    being served: it never rides the process-wide plan cache, and the
    certify path re-attaches it even when the layout signature matches.
    """
    row_ids: jax.Array    # (G, capM) int32 — rows assigned to each group
    col_ids: jax.Array    # (G, capN) int32
    row_valid: jax.Array  # (G, capM) bool — padding slots are False
    col_valid: jax.Array  # (G, capN) bool
    row_group: jax.Array  # (M,) int32 — balanced group of each row
    col_group: jax.Array  # (N,) int32
    wc: Optional[jax.Array] = None  # (G, capM, capN) compact weights


def balanced_assign(scores: jax.Array, axis: int,
                    slack: float = 1.0) -> jax.Array:
    """Deal items into equal-capacity groups by argmax preference.

    ``scores``: (..., M, G) if axis==1 (rows of IG) or (..., G, N) if
    axis==0 (columns of OG); leading dims batch over stacked layers.
    Returns (..., G, cap) int32 item indices with
    ``cap = ceil(M/G · slack)``.

    Items keep their argmax group as long as it has a free slot (the
    ``slack`` headroom makes that the common case — exactly the MoE
    capacity-factor trade); only true overflow items — the *least*
    confident ones of an over-popular group — spill into other groups'
    free slots. ``slack == 1.0`` reproduces the strict equal-deal.

    Runs on the ``plan_encode`` Pallas kernel (comparator-rank counting
    sort; the lexsort reference is preserved in
    ``repro.kernels.plan_encode.ref`` and used under reference-impl mode).
    """
    return pe_ops.balanced_assign(scores, axis, slack)


def _group_of_item(ids: jax.Array, size: int) -> jax.Array:
    """(..., G, cap) item ids -> (..., size) group of each item (inverse
    lookup via scatter; padded slots were clipped into range upstream)."""
    lead = ids.shape[:-2]
    g = ids.shape[-2]
    gid = jnp.broadcast_to(
        jnp.arange(g, dtype=jnp.int32)[:, None], ids.shape[-2:]).reshape(-1)
    if not lead:
        return (jnp.zeros((size,), jnp.int32)
                .at[ids.reshape(-1)].set(gid, mode="drop"))
    length = int(np.prod(lead))
    flat = ids.reshape(length, -1)
    out = (jnp.zeros((length, size), jnp.int32)
           .at[jnp.arange(length)[:, None], flat]
           .set(jnp.broadcast_to(gid[None], flat.shape), mode="drop"))
    return out.reshape(*lead, size)


def make_plan(ig: jax.Array, og: jax.Array,
              slack: float = 1.0) -> GroupPlan:
    """Build the compact layout from the grouping matrices.

    ``ig``: (..., M, G), ``og``: (..., G, N) — leading dims (the stacked
    scan-layer axis of the LM decoder) batch through the plan-encode
    kernel's grid in one launch; every GroupPlan leaf comes back with the
    same leading dims.
    """
    m = ig.shape[-2]
    n = og.shape[-1]
    row_ids = balanced_assign(ig, axis=1, slack=slack)   # (..., G, capM)
    col_ids = balanced_assign(og, axis=0, slack=slack)   # (..., G, capN)
    row_valid = row_ids < m
    col_valid = col_ids < n
    row_ids = jnp.minimum(row_ids, m - 1)
    col_ids = jnp.minimum(col_ids, n - 1)
    return GroupPlan(row_ids, col_ids, row_valid, col_valid,
                     _group_of_item(row_ids, m), _group_of_item(col_ids, n))


def transpose_plan(plan: GroupPlan) -> GroupPlan:
    """Plan of Mask^T — the weight-transpose trick on cached metadata.

    ``make_plan(og.T, ig.T)`` is exactly the row/col swap of
    ``make_plan(ig, og)`` (``balanced_assign(og, axis=0) ==
    balanced_assign(og.T, axis=1)``), so the transposed layout is free:
    no re-encoding, matching the paper's transposed-encode reuse (§III-B).
    """
    wc = None if plan.wc is None else jnp.swapaxes(plan.wc, -1, -2)
    return GroupPlan(row_ids=plan.col_ids, col_ids=plan.row_ids,
                     row_valid=plan.col_valid, col_valid=plan.row_valid,
                     row_group=plan.col_group, col_group=plan.row_group,
                     wc=wc)


# ---------------------------------------------------------------------------
# RawPlans: one GroupPlan per FLGW layer of a param tree (OSEL analogue)
# ---------------------------------------------------------------------------

# RawPlans mirrors a params pytree: nested dict whose leaves are the
# GroupPlan of every projection dict carrying ig/og grouping matrices.
# (repro.core.encoder.PlanState wraps this dict with the argmax signature
# used for change-driven refresh — that is the type most callers handle.)
RawPlans = dict[str, Any]


def iter_flgw_layers(params: dict, _path=()):
    """Yield ``(path, layer_dict)`` for every FLGW-carrying projection —
    any nested dict holding ``ig``/``og`` grouping matrices. The single
    source of truth for walking a param tree's FLGW structure.

    Iterates in sorted key order — the same canonical order jit's pytree
    flattening gives dicts — so order-sensitive consumers (the plan
    signature's per-layer salts) agree between eager and traced calls."""
    for name, p in sorted(params.items()):
        if not isinstance(p, dict):
            continue
        if "ig" in p:
            yield (*_path, name), p
        else:
            yield from iter_flgw_layers(p, (*_path, name))


def encode_plans(params: dict, cfg) -> RawPlans:
    """One encoding pass over a param tree — the OSEL loop's TPU analogue.

    The paper encodes the FLGW mask *once per iteration* into compact
    sparse metadata that the whole forward/backward then reuses (§III-B).
    Here that metadata is the capacity-balanced :class:`GroupPlan`; this
    builds one per FLGW-carrying projection so callers can cache and
    re-encode it on their own schedule instead of re-deriving it inside
    every projection. The dict mirrors the params nesting; stacked
    (scanned) layers encode in one batched kernel launch and get plans
    stacked along the same leading axes.

    This returns the raw plans dict; most callers want
    :func:`repro.core.encoder.encode_plans`, which pairs it with the
    argmax signature used for change-driven refresh.
    """
    plans: RawPlans = {}
    for path, p in iter_flgw_layers(params):
        node = plans
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = make_plan(p["ig"], p["og"], cfg.capacity_slack)
    return plans


def _map_plans(plans: RawPlans, params: dict, fn) -> RawPlans:
    """Rebuild ``plans`` with ``fn(plan, layer_params)`` at every FLGW
    projection, walking params and plans in lockstep."""
    out: RawPlans = {}
    for path, p in iter_flgw_layers(params):
        node_in, node_out = plans, out
        for name in path[:-1]:
            node_in = node_in[name]
            node_out = node_out.setdefault(name, {})
        node_out[path[-1]] = fn(node_in[path[-1]], p)
    return out


def attach_compact(plans: RawPlans, params: dict) -> RawPlans:
    """Attach the compact weights ``W_c`` to every plan — the weight half
    of the paper's OSEL encode output (§III-B: the encoder emits the
    sparse *data*, not just indices, and the cores consume it directly).

    One XLA gather per projection, amortized over every consume until the
    params move; :func:`grouped_apply` then feeds ``wc`` to the kernel
    as-is and gathers only the activations. ``wc`` snapshots
    weight *values*: re-attach whenever params change (the plan signature
    does **not** cover it — see :class:`GroupPlan`). Stacked/scanned and
    vmapped-expert layers attach along their leading dims unchanged.
    """
    def _one(plan: GroupPlan, p: dict) -> GroupPlan:
        wc = kops.compact_weights(p["w"], plan.row_ids, plan.col_ids,
                                  plan.row_valid, plan.col_valid)
        return plan._replace(wc=wc)
    return _map_plans(plans, params, _one)


def strip_compact(plans: RawPlans) -> RawPlans:
    """Drop every plan's ``wc`` — back to the pure-layout (int/bool) tree
    that training carries and the process-wide plan cache may hold."""
    return jax.tree.map(
        lambda p: p._replace(wc=None) if isinstance(p, GroupPlan) else p,
        plans, is_leaf=lambda p: isinstance(p, GroupPlan))


def has_compact(plans) -> bool:
    """Whether any plan in the tree carries attached compact weights."""
    found = False
    def _look(p):
        nonlocal found
        if isinstance(p, GroupPlan) and p.wc is not None:
            found = True
        return p
    jax.tree.map(_look, plans, is_leaf=lambda p: isinstance(p, GroupPlan))
    return found


# ---------------------------------------------------------------------------
# Compact apply with custom VJP
# ---------------------------------------------------------------------------

def _gather_x(x, plan: GroupPlan):
    b = x.shape[0]
    g, cap_m = plan.row_ids.shape
    xg = jnp.take(x, plan.row_ids.reshape(-1), axis=1)
    xg = xg.reshape(b, g, cap_m).transpose(1, 0, 2)
    return jnp.where(plan.row_valid[:, None, :], xg, 0)


def _gather_w(w, plan: GroupPlan):
    wc = w[plan.row_ids[:, :, None], plan.col_ids[:, None, :]]
    return jnp.where(plan.row_valid[:, :, None] & plan.col_valid[:, None, :],
                     wc, 0)


def _core_matmul(x, w, plan: GroupPlan, interpret, impl):
    """One compact product. Plans carrying attached compact weights skip
    the per-call W gather (``plan.wc`` feeds the kernel as-is); bare plans
    gather W per call; the jnp reference stays the GSPMD-shardable
    fallback. Cached and gathered ``W_c`` are the same values, so the two
    kernel paths agree bitwise."""
    return kops.grouped_matmul(x, w, plan.row_ids, plan.col_ids,
                               plan.row_valid, plan.col_valid, plan.wc,
                               interpret=interpret, impl=impl)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_core(x, w, ig, og, plan: GroupPlan, temperature: float,
                  interpret: bool, impl: str):
    """Compact matmul against *precomputed* sparse metadata.

    The plan is a VJP input (not rebuilt in fwd/bwd): the backward pass
    reuses the very same metadata via the transpose trick, so one encode
    serves the whole step — the paper's OSEL amortization.
    """
    return _core_matmul(x, w, plan, interpret, impl)


def _grouped_fwd(x, w, ig, og, plan, temperature, interpret, impl):
    y = _core_matmul(x, w, plan, interpret, impl)
    return y, (x, w, ig, og, plan)


def _grouped_bwd(temperature, interpret, impl, res, gy):
    x, w, ig, og, plan = res
    b = x.shape[0]
    m, g = ig.shape
    n = og.shape[1]
    cap_m = plan.row_ids.shape[1]
    cap_n = plan.col_ids.shape[1]

    xg = constrain(_gather_x(x, plan), (None, "batch", None))
    wc = plan.wc if plan.wc is not None else _gather_w(w, plan)
    wc = constrain(wc, (None, None, "flgw_cap"))
    gc = jnp.take(gy, plan.col_ids.reshape(-1), axis=1)  # (B, G*capN)
    gc = gc.reshape(b, g, cap_n).transpose(1, 0, 2)      # (G, B, capN)
    gc = jnp.where(plan.col_valid[:, None, :], gc, 0)
    gc = constrain(gc, (None, "batch", "flgw_cap"))

    # dX: transposed compact product — the paper's weight-transpose trick:
    # Mask^T has the same structure with IG/OG swapped, so we reuse the
    # compact tiles with the contraction flipped.
    dxc = jnp.einsum("gbn,gmn->gbm", gc, wc,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    flat_rows = jnp.where(plan.row_valid, plan.row_ids, m).reshape(-1)
    dx = (jnp.zeros((b, m), x.dtype)
          .at[:, flat_rows]
          .set(dxc.transpose(1, 0, 2).reshape(b, -1), mode="drop"))

    # dW: compact outer products scattered to the dense weight.
    dwc = jnp.einsum("gbm,gbn->gmn", xg, gc,
                     preferred_element_type=jnp.float32).astype(w.dtype)
    dw = (jnp.zeros((m, n), w.dtype)
          .at[plan.row_ids[:, :, None], plan.col_ids[:, None, :]]
          .add(dwc, mode="drop"))

    # dIG/dOG: sparse-restricted STE. The mask gradient on surviving entries
    # is dMask = dW ⊙ W; reduce it to per-row / per-column scalars and push
    # through the softmax Jacobian at the assigned group.
    s_rows_c = jnp.sum(dwc * wc, axis=2)                 # (G, capM)
    s_row = (jnp.zeros((m,), jnp.float32)
             .at[flat_rows.reshape(g, cap_m)]
             .add(s_rows_c.astype(jnp.float32), mode="drop"))
    s_cols_c = jnp.sum(dwc * wc, axis=1)                 # (G, capN)
    flat_cols = jnp.where(plan.col_valid, plan.col_ids, n).reshape(-1)
    s_col = (jnp.zeros((n,), jnp.float32)
             .at[flat_cols.reshape(g, cap_n)]
             .add(s_cols_c.astype(jnp.float32), mode="drop"))

    tau = temperature
    soft_ig = jax.nn.softmax(ig / tau, axis=1)           # (M, G)
    pg_row = jax.nn.one_hot(plan.row_group, g, dtype=soft_ig.dtype)
    sel_r = jnp.sum(soft_ig * pg_row, axis=1, keepdims=True)
    dig = (s_row[:, None] / tau) * sel_r * (pg_row - soft_ig)
    soft_og = jax.nn.softmax(og / tau, axis=0)           # (G, N)
    pg_col = jax.nn.one_hot(plan.col_group, g, dtype=soft_og.dtype, axis=0)
    sel_c = jnp.sum(soft_og * pg_col, axis=0, keepdims=True)
    dog = (s_col[None, :] / tau) * sel_c * (pg_col - soft_og)

    # Plan entries are metadata: int/bool leaves get float0 cotangents; an
    # attached ``wc`` (a float snapshot derived from w) gets symbolic
    # zeros — the full weight gradient already flows through ``dw``.
    dplan = jax.tree.map(
        lambda a: (jnp.zeros(a.shape, a.dtype)
                   if jnp.issubdtype(a.dtype, jnp.inexact)
                   else np.zeros(a.shape, jax.dtypes.float0)), plan)
    return dx, dw, dig.astype(ig.dtype), dog.astype(og.dtype), dplan


_grouped_core.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_apply(x: jax.Array, w: jax.Array, ig: jax.Array, og: jax.Array,
                  cfg, *, transpose: bool = False,
                  plan: Optional[GroupPlan] = None) -> jax.Array:
    """Compact FLGW linear. ``x``: (..., M) (or (..., N) when transposed).

    ``plan`` is the cached sparse metadata of the *untransposed* layer
    (see :func:`encode_plans`); when omitted the plan is re-derived here —
    the unamortized fallback, one encode per projection call.
    """
    interpret = default_interpret()
    impl = "reference" if kops._REF_MODE else "pallas"
    if transpose:
        # y = x @ (W ⊙ M)^T == grouped(x, W^T) with IG/OG roles swapped.
        w_t, ig_t, og_t = w.T, og.T, ig.T
        plan = transpose_plan(plan) if plan is not None else None
    else:
        w_t, ig_t, og_t = w, ig, og
    if plan is None:
        plan = make_plan(ig_t, og_t, cfg.capacity_slack)
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    y = _grouped_core(xf, w_t, ig_t, og_t, plan, cfg.ste_temperature,
                      interpret, impl)
    return y.reshape(*lead, -1)
