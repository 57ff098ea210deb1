"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grouped import make_plan
from repro.kernels.flgw_matmul import ops as fops
from repro.kernels.flgw_matmul import ref as fref
from repro.kernels.flgw_matmul.flgw_matmul import grouped_bmm
from repro.kernels.osel_encode import ops as oops
from repro.kernels.osel_encode import ref as oref
from repro.kernels.osel_encode.osel_encode import encode_mask


def _tol(dtype):
    # f32: accumulation-order differences between the tiled kernel and a
    # single einsum reach ~1e-5 absolute on 256-deep contractions.
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=5e-5)


# ---------------------------------------------------------------------------
# grouped_bmm: the raw Pallas block-diagonal matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,b,m,n", [
    (1, 8, 128, 128), (4, 16, 128, 256), (8, 128, 256, 128),
    (2, 8, 384, 128), (16, 8, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_bmm_matches_einsum(g, b, m, n, dtype):
    key = jax.random.PRNGKey(g * 1000 + b + m + n)
    xg = jax.random.normal(key, (g, b, m), jnp.float32).astype(dtype)
    wc = jax.random.normal(jax.random.fold_in(key, 1), (g, m, n),
                           jnp.float32).astype(dtype)
    bb = min(128, b)
    got = grouped_bmm(xg, wc, bb=bb, bn=128, bk=128, interpret=True)
    want = fref.ref_grouped_bmm(xg, wc)
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# grouped_matmul: gather -> kernel -> scatter wrapper
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,g,b", [
    (64, 64, 4, 8), (96, 128, 2, 4),
    pytest.param(128, 96, 8, 16, marks=pytest.mark.slow),
    pytest.param(256, 256, 16, 8, marks=pytest.mark.slow),
    pytest.param(80, 48, 4, 3, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_ref(m, n, g, b, dtype):
    key = jax.random.PRNGKey(m + n + g + b)
    x = jax.random.normal(key, (b, m), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m, n),
                          jnp.float32).astype(dtype)
    ig = jax.random.normal(jax.random.fold_in(key, 2), (m, g))
    og = jax.random.normal(jax.random.fold_in(key, 3), (g, n))
    plan = make_plan(ig, og)
    got = fops.grouped_matmul(x, w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid, interpret=True)
    want = fref.ref_grouped_matmul(x, w, plan.row_ids, plan.col_ids,
                                   plan.row_valid, plan.col_valid)
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32), **_tol(dtype))


def test_grouped_matmul_balanced_plan_equals_masked_oracle():
    """When each group has exactly cap rows/cols, the compact path must
    reproduce the paper's masked matmul exactly."""
    m = n = 64
    g = 4
    key = jax.random.PRNGKey(0)
    # permutation-structured IG/OG: exactly m/g rows per group
    row_groups = jnp.tile(jnp.arange(g), m // g)
    col_groups = jnp.tile(jnp.arange(g), n // g)
    ig = jax.nn.one_hot(row_groups, g) * 10.0
    og = jax.nn.one_hot(col_groups, g, axis=0).reshape(g, n) * 10.0
    w = jax.random.normal(key, (m, n))
    x = jax.random.normal(jax.random.fold_in(key, 1), (5, m))
    plan = make_plan(ig, og)
    got = fops.grouped_matmul(x, w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid, interpret=True)
    want = fref.ref_masked_matmul(x, w, row_groups.astype(jnp.int32),
                                  col_groups.astype(jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# cached compact weights: GroupPlan.wc fed to grouped_matmul as-is
# ---------------------------------------------------------------------------

def _fused_pair(m, n, g, b, slack, dtype, seed=None):
    key = jax.random.PRNGKey(seed if seed is not None else m + n + g + b)
    x = jax.random.normal(key, (b, m), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m, n),
                          jnp.float32).astype(dtype)
    ig = jax.random.normal(jax.random.fold_in(key, 2), (m, g))
    og = jax.random.normal(jax.random.fold_in(key, 3), (g, n))
    return x, w, make_plan(ig, og, slack)


@pytest.mark.parametrize("m,n,g,b,slack", [
    (64, 64, 4, 8, 1.0), (96, 128, 2, 4, 1.0), (160, 96, 8, 7, 1.3),
    pytest.param(256, 256, 16, 8, 1.0, marks=pytest.mark.slow),
    pytest.param(300, 200, 4, 16, 1.5, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bitwise_matches_gather_path(m, n, g, b, slack, dtype):
    """The serving consume path (compact ``W_c`` cached beside the plan
    and fed to the kernel as-is) is *bitwise* equal to the per-call
    weight gather — the kernel sees the same operands either way — so
    callers can flip paths per call with no parity budget."""
    x, w, plan = _fused_pair(m, n, g, b, slack, dtype)
    wc = fops.compact_weights(w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid)
    got = fops.grouped_matmul(x, w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid, wc,
                              interpret=True)
    want = fops.grouped_matmul(x, w, plan.row_ids, plan.col_ids,
                               plan.row_valid, plan.col_valid,
                               interpret=True)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compact_weights_zeroes_invalid_slots():
    """Invalid (padding) slots of W_c are zero, exactly as the per-call
    gather in ``grouped_matmul`` masks them."""
    _, w, plan = _fused_pair(80, 48, 4, 3, 1.5, jnp.float32, seed=9)
    wc = fops.compact_weights(w, plan.row_ids, plan.col_ids,
                              plan.row_valid, plan.col_valid)
    g, cap_m, cap_n = wc.shape
    assert (cap_m, cap_n) == (plan.row_ids.shape[1], plan.col_ids.shape[1])
    invalid = ~(np.asarray(plan.row_valid)[:, :, None]
                & np.asarray(plan.col_valid)[:, None, :])
    assert (np.asarray(wc)[invalid] == 0).all()
    # valid slots are the straight double-gather of W
    rid, cid = np.asarray(plan.row_ids), np.asarray(plan.col_ids)
    want = np.asarray(w)[rid[:, :, None], cid[:, None, :]]
    np.testing.assert_array_equal(np.where(invalid, 0, want), np.asarray(wc))


def test_compact_weights_stacked_layers_fold_through_vmap():
    """Stacked (scanned-decoder) leading dims: compact_weights vmaps and
    each layer's slice is bitwise the per-layer call."""
    layers = []
    for i in range(3):
        x, w, plan = _fused_pair(64, 96, 4, 5, 1.25, jnp.float32, seed=40 + i)
        layers.append((w, plan))
    ws = jnp.stack([w for w, _ in layers])
    stack = lambda f: jnp.stack([f(p) for _, p in layers])  # noqa: E731
    wcs = fops.compact_weights(ws, stack(lambda p: p.row_ids),
                               stack(lambda p: p.col_ids),
                               stack(lambda p: p.row_valid),
                               stack(lambda p: p.col_valid))
    for i, (w, plan) in enumerate(layers):
        one = fops.compact_weights(w, plan.row_ids, plan.col_ids,
                                   plan.row_valid, plan.col_valid)
        np.testing.assert_array_equal(np.asarray(wcs[i]), np.asarray(one))


# ---------------------------------------------------------------------------
# osel_encode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(8, 8), (128, 512), (300, 200), (1, 64),
                                 (257, 129)])
@pytest.mark.parametrize("g", [2, 4, 16])
def test_encode_mask_kernel_matches_ref(m, n, g):
    key = jax.random.PRNGKey(m * n + g)
    ig_idx = jax.random.randint(key, (m,), 0, g, jnp.int32)
    og_idx = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, g,
                                jnp.int32)
    got = encode_mask(ig_idx, og_idx, interpret=True)
    want = oref.ref_mask_indices(ig_idx, og_idx)
    np.testing.assert_array_equal(np.asarray(got) > 0, np.asarray(want))


def test_osel_mask_wrapper_vs_matmul_baseline():
    """Kernel output == the baseline IS @ OS mask from raw matrices."""
    key = jax.random.PRNGKey(5)
    ig = jax.random.normal(key, (64, 8))
    og = jax.random.normal(jax.random.fold_in(key, 1), (8, 96))
    ig_idx = jnp.argmax(ig, axis=1).astype(jnp.int32)
    og_idx = jnp.argmax(og, axis=0).astype(jnp.int32)
    got = oops.osel_mask(ig_idx, og_idx, interpret=True)
    want = oops.reference_mask(ig, og)
    np.testing.assert_array_equal(np.asarray(got) > 0, np.asarray(want))
