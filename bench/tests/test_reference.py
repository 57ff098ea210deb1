"""The plain reference's FLGW layer against its own definition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import flgw


@pytest.mark.parametrize("m,g,slack", [(20, 4, 1.0), (13, 4, 1.25), (128, 4, 1.25)])
def test_deal_fills_groups_within_capacity(m, g, slack):
    scores = jax.random.normal(jax.random.PRNGKey(m), (m, g))
    groups = np.asarray(flgw.deal(scores, slack))
    cap = flgw.capacity(m, g, slack)
    counts = np.bincount(groups, minlength=g)
    assert groups.shape == (m,) and counts.sum() == m
    assert counts.max() <= cap
    pref = np.asarray(jnp.argmax(scores, axis=1))
    for k in range(g):
        # a group that is not full lost none of the items that prefer it
        if (pref == k).sum() <= cap:
            assert np.all(groups[pref == k] == k)


def test_linear_is_the_masked_product_with_its_gradients():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (6, 12))
    w = jax.random.normal(k[1], (12, 8))
    ig = jax.random.normal(k[2], (12, 4))
    og = jax.random.normal(k[3], (4, 8))
    mm = jnp.matmul
    _, _, mask = flgw.masks(ig, og, 1.25)

    def masked(x, w):
        return jnp.sum(jnp.sin(x @ jnp.where(mask, w, 0)))

    def layer(x, w):
        return jnp.sum(jnp.sin(flgw.linear(x, w, ig, og, 1.25, 1.0, mm)))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(masked, argnums=(0, 1))(x, w)
        got = jax.grad(layer, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(layer(x, w), masked(x, w), rtol=1e-6)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
