"""Unit + property tests for the compression substrate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyputil import given, settings, st

from repro.core import compression as C

SEED = st.integers(0, 2**31 - 1)


def _vec(key, n):
    return jax.random.normal(jax.random.PRNGKey(key), (n,))


class TestTopK:
    def test_exact_k(self):
        u = _vec(0, 1000)
        c = C.topk_compress(u, 0.1)
        assert int(c.mask.sum()) == 100

    def test_keeps_largest(self):
        u = jnp.asarray(np.random.default_rng(0).permutation(1000.0 + np.arange(1000)))
        c = C.topk_compress(u, 0.05)
        kept = np.sort(np.asarray(u)[np.asarray(c.mask)])
        assert kept.min() >= np.sort(np.asarray(u))[-50]

    def test_values_masked(self):
        u = _vec(1, 512)
        c = C.topk_compress(u, 0.25)
        np.testing.assert_array_equal(np.asarray(c.values == 0),
                                      ~np.asarray(c.mask))

    @given(st.integers(10, 5000), st.floats(0.01, 1.0), SEED)
    @settings(max_examples=25, deadline=None)
    def test_property_retained_count(self, n, cr, seed):
        u = jax.random.normal(jax.random.PRNGKey(seed), (n,))
        c = C.topk_compress(u, cr)
        k = C.k_for_ratio(n, cr)
        assert int(c.mask.sum()) == k  # distinct gaussian values: no ties

    @given(st.integers(100, 3000), st.floats(0.05, 0.9), SEED)
    @settings(max_examples=25, deadline=None)
    def test_property_mass_dominance(self, n, cr, seed):
        """Top-K retains at least cr fraction of the L2 mass (it is the
        best k-sparse approximation)."""
        u = jax.random.normal(jax.random.PRNGKey(seed), (n,))
        c = C.topk_compress(u, cr)
        kept = float(jnp.sum(c.values ** 2))
        total = float(jnp.sum(u ** 2))
        assert kept >= cr * total - 1e-5


class TestDynamicTopK:
    @given(st.integers(16, 4000), st.integers(1, 200), SEED)
    @settings(max_examples=25, deadline=None)
    def test_matches_static(self, n, k, seed):
        k = min(k, n)
        u = jax.random.normal(jax.random.PRNGKey(seed), (n,))
        dyn = C.topk_compress_dynamic(u, jnp.int32(k))
        mag = jnp.abs(u)
        thresh = jax.lax.top_k(mag, k)[0][-1]
        ref_mask = mag >= thresh
        np.testing.assert_array_equal(np.asarray(dyn.mask), np.asarray(ref_mask))


class TestErrorFeedback:
    def test_conservation(self):
        """send + residual' == residual + g (nothing is lost)."""
        g, e = _vec(6, 4096), _vec(7, 4096)
        comp, new_e = C.ef_compress(e, g, 0.1)
        np.testing.assert_allclose(np.asarray(comp.values + new_e),
                                   np.asarray(e + g), rtol=1e-6)

    def test_residual_decays_for_stationary_grad(self):
        """With a repeated gradient, EF eventually transmits everything:
        total sent over T rounds -> T*g."""
        g = _vec(8, 2048)
        e = jnp.zeros_like(g)
        sent = jnp.zeros_like(g)
        for _ in range(50):
            comp, e = C.ef_compress(e, g, 0.05)
            sent = sent + comp.values
        # the residual is bounded, so sent/T -> g
        np.testing.assert_allclose(np.asarray(sent + e), np.asarray(g * 50),
                                   rtol=1e-4)


class TestSparseFormat:
    @given(st.integers(64, 2000), st.floats(0.02, 0.5), SEED)
    @settings(max_examples=20, deadline=None)
    def test_roundtrip(self, n, cr, seed):
        u = jax.random.normal(jax.random.PRNGKey(seed), (n,))
        c = C.topk_compress(u, cr)
        k = C.k_for_ratio(n, cr)
        idx, vals = C.to_sparse(c, k)
        dense = C.from_sparse(idx, vals, n)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(c.values),
                                   rtol=1e-6)

    @given(st.integers(64, 2000), st.integers(1, 500), SEED)
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_dynamic(self, n, k, seed):
        """Wire-format round-trip over the traced-k compressor (the fused
        round's selection path)."""
        k = min(k, n)
        u = jax.random.normal(jax.random.PRNGKey(seed), (n,))
        c = C.topk_compress_dynamic(u, jnp.int32(k))
        idx, vals = C.to_sparse(c, k)
        dense = C.from_sparse(idx, vals, n)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(c.values),
                                   rtol=1e-6)

    def test_overallocated_k(self):
        u = _vec(9, 256)
        c = C.topk_compress(u, 0.05)
        idx, vals = C.to_sparse(c, 64)  # k larger than retained count
        assert int((idx >= 0).sum()) == int(c.mask.sum())
        dense = C.from_sparse(idx, vals, 256)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(c.values),
                                   rtol=1e-6)


class TestQuantize:
    def test_unbiased(self):
        u = _vec(10, 10000)
        keys = jax.random.split(jax.random.PRNGKey(11), 64)
        qs = jnp.stack([C.quantize_stochastic(u, 4, k) for k in keys])
        err = np.asarray(qs.mean(0)) - np.asarray(u)
        # unbiasedness: mean error ~ 0; pointwise error within ~5 sigma of
        # the Bernoulli rounding noise (scale/2/sqrt(64))
        assert abs(err.mean()) < 0.01
        scale = float(jnp.max(jnp.abs(u))) / 7
        assert np.abs(err).max() < 5 * scale * 0.5 / 8

    def test_reconstruction_error_bounded(self):
        u = _vec(14, 4096)
        q = C.quantize_stochastic(u, 8, jax.random.PRNGKey(15))
        scale = float(jnp.max(jnp.abs(u))) / 127
        assert float(jnp.max(jnp.abs(q - u))) <= scale * (1 + 1e-6)


class TestRandK:
    def test_unbiased_scaling(self):
        u = _vec(12, 5000)
        keys = jax.random.split(jax.random.PRNGKey(13), 200)
        est = jnp.stack([C.randk_compress(u, 0.2, k).values for k in keys])
        err = np.asarray(est.mean(0)) - np.asarray(u)
        assert abs(err.mean()) < 0.02          # unbiased on average
        assert np.abs(err).mean() < 0.2        # bounded estimator noise
