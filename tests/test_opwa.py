"""OPWA tests (paper §4.3, Alg. 3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyputil import given, settings, st

from repro.core import compression as C
from repro.core import opwa


def _sparse_updates(k_clients, n, cr, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), k_clients)
    vals, masks = [], []
    for kk in keys:
        u = jax.random.normal(kk, (n,))
        c = C.topk_compress(u, cr)
        vals.append(c.values)
        masks.append(c.mask)
    return jnp.stack(vals), jnp.stack(masks)


class TestOverlap:
    def test_counts_range(self):
        vals, masks = _sparse_updates(5, 2000, 0.1)
        counts = opwa.overlap_counts(masks)
        assert counts.min() >= 0 and counts.max() <= 5

    def test_mask_values(self):
        counts = jnp.array([0, 1, 2, 3, 5])
        m = opwa.opwa_mask(counts, gamma=4.0, d=2)
        np.testing.assert_array_equal(np.asarray(m), [1.0, 4.0, 4.0, 1.0, 1.0])

    def test_fig4_pattern_majority_singletons_at_high_compression(self):
        """Paper Fig. 4: at CR=0.01 most retained indices appear in only one
        client's update (random-ish top-k supports barely overlap)."""
        vals, masks = _sparse_updates(5, 50_000, 0.01, seed=2)
        counts = np.asarray(opwa.overlap_counts(masks))
        retained = counts[counts > 0]
        frac_singleton = (retained == 1).mean()
        assert frac_singleton > 0.5

    def test_overlap_grows_with_cr(self):
        """Among RETAINED indices, the singleton fraction falls as CR rises
        (paper Fig. 4: high compression -> mostly overlap-1)."""
        _, m_low = _sparse_updates(5, 20_000, 0.01, seed=3)
        _, m_high = _sparse_updates(5, 20_000, 0.3, seed=3)
        c_low = np.asarray(opwa.overlap_counts(m_low))
        c_high = np.asarray(opwa.overlap_counts(m_high))
        f1 = (c_low[c_low > 0] == 1).mean()
        f2 = (c_high[c_high > 0] == 1).mean()
        assert f2 < f1


class TestHistogram:
    def test_matches_manual_loop(self):
        _, masks = _sparse_updates(5, 3000, 0.1, seed=11)
        hist = np.asarray(opwa.overlap_histogram(masks))
        counts = np.asarray(opwa.overlap_counts(masks))
        manual = np.array([np.sum(counts == c) for c in range(6)])
        np.testing.assert_array_equal(hist, manual)

    def test_sums_to_n(self):
        _, masks = _sparse_updates(4, 2048, 0.2, seed=12)
        hist = np.asarray(opwa.overlap_histogram(masks))
        assert hist.sum() == 2048

    def test_kmax_truncates(self):
        """Degrees above k_max are dropped, not clipped into the last bin."""
        masks = jnp.ones((5, 7), bool)   # every index has overlap 5
        hist = np.asarray(opwa.overlap_histogram(masks, k_max=3))
        np.testing.assert_array_equal(hist, [0, 0, 0, 0])


class TestAggregate:
    def test_equals_manual(self):
        vals, masks = _sparse_updates(4, 1000, 0.1)
        coeffs = jnp.array([0.1, 0.2, 0.3, 0.4])
        agg = opwa.opwa_aggregate(vals, masks, coeffs, gamma=3.0, d=1)
        counts = np.asarray(masks.astype(np.int32)).sum(0)
        man = np.einsum("k,kn->n", np.asarray(coeffs), np.asarray(vals, np.float32))
        man = np.where((counts > 0) & (counts <= 1), 3.0 * man, man)
        np.testing.assert_allclose(np.asarray(agg), man, rtol=1e-5)

    def test_gamma_one_is_bcrs(self):
        vals, masks = _sparse_updates(4, 1000, 0.1, seed=5)
        coeffs = jnp.array([0.25] * 4)
        a = opwa.opwa_aggregate(vals, masks, coeffs, gamma=1.0, d=1)
        b = opwa.bcrs_aggregate(vals, coeffs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    @given(st.integers(2, 8), st.floats(1.0, 10.0), st.integers(0, 100))
    @settings(max_examples=15, deadline=None)
    def test_property_amplifies_only_low_overlap(self, k, gamma, seed):
        vals, masks = _sparse_updates(k, 3000, 0.05, seed=seed)
        coeffs = jnp.ones((k,)) / k
        with_g = np.asarray(opwa.opwa_aggregate(vals, masks, coeffs, gamma, 1))
        no_g = np.asarray(opwa.bcrs_aggregate(vals, coeffs))
        counts = np.asarray(opwa.overlap_counts(masks))
        hi = counts > 1
        np.testing.assert_allclose(with_g[hi], no_g[hi], rtol=1e-5)
        lo = counts == 1
        np.testing.assert_allclose(with_g[lo], gamma * no_g[lo], rtol=1e-4)
