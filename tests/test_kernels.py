"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp
oracle, and the merge kernels through ``compress_merge_leaf``'s two routes
(kernel vs jnp, bit for bit)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import strategies
from repro.fed.engine import compress_merge_leaf
from repro.kernels import ops, ref

SHAPES_2D = [(8, 128), (8, 1024), (16, 8192), (32, 512), (8, 2048)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _routes(strategy, u, w, ks, residuals=None, gamma=5.0, d=1):
    """compress_merge_leaf through the jnp route and the kernel route."""
    strat = strategies.get(strategy)
    return [compress_merge_leaf(u, w, ks, strat, gamma=gamma, overlap_d=d,
                                use_kernel=uk, residuals=residuals)
            for uk in (False, True)]


def _assert_routes_equal(outs):
    (agg_j, res_j), (agg_k, res_k) = outs
    np.testing.assert_array_equal(np.asarray(agg_k), np.asarray(agg_j))
    if res_j is not None:
        np.testing.assert_array_equal(np.asarray(res_k), np.asarray(res_j))


class TestThresholdFindGrid:
    """``ops.topk_thresholds`` against the bisection oracle over [C, n]
    shapes, in f32 and in bf16 deltas cast to f32 (what every cell's
    bf16 model feeds the merge)."""

    @pytest.mark.parametrize("shape", SHAPES_2D)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_vs_ref(self, shape, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
        x = x.astype(jnp.float32)
        c, n = shape
        ks = jnp.full((c,), max(1, n // 10), jnp.int32).at[0].set(1)
        np.testing.assert_array_equal(
            np.asarray(ops.topk_thresholds(x, ks)),
            np.asarray(ref.threshold_find_ref(x, ks))[:, 0])

    @pytest.mark.parametrize("k", [1, 7, 128, 1024])
    def test_k_sweep(self, k):
        """Both routes keep exactly k per client: the survivors are the
        EF residual's exact zeros."""
        u = jax.random.normal(jax.random.PRNGKey(1), (8, 1024))
        e = jax.random.normal(jax.random.PRNGKey(2), (8, 1024)) * 0.3
        w = jnp.full((8,), 1 / 8)
        outs = _routes("eftopk", u, w, jnp.full((8,), k, jnp.int32),
                       residuals=e)
        _assert_routes_equal(outs)
        kept = np.sum(np.asarray(outs[1][1]) == 0.0, axis=1)
        assert (kept == k).all()


class TestOPWAMergeRoutes:
    """The OPWA merge (counts, gamma mask, weighted sum) of ``bcrs_opwa``
    leaves: kernel route against jnp route, bit for bit."""

    @pytest.mark.parametrize("k_clients", [2, 5, 10, 16])
    @pytest.mark.parametrize("n", [1024, 4096, 10240])
    def test_vs_jnp(self, k_clients, n):
        key = jax.random.PRNGKey(k_clients * 1000 + n)
        u = jax.random.normal(key, (k_clients, n))
        w = jax.random.uniform(jax.random.PRNGKey(2), (k_clients,))
        ks = jnp.full((k_clients,), n // 10, jnp.int32)
        _assert_routes_equal(_routes("bcrs_opwa", u, w, ks))

    @pytest.mark.parametrize("gamma,d", [(1.0, 1), (3.0, 2), (10.0, 1)])
    def test_gamma_d_sweep(self, gamma, d):
        u = jax.random.normal(jax.random.PRNGKey(3), (6, 2048))
        w = jnp.full((6,), 1 / 6)
        ks = jnp.asarray([100, 200, 300, 400, 500, 600], jnp.int32)
        _assert_routes_equal(_routes("bcrs_opwa", u, w, ks, gamma=gamma,
                                     d=d))


class TestEFMergeRoutes:
    """``eftopk`` leaves of a 4-client cohort: the error-feedback step on
    both routes (the kernel route on the leaf's [C, n] view), bit for bit,
    aggregate and new residuals."""

    @pytest.mark.parametrize("shape", SHAPES_2D)
    def test_vs_jnp(self, shape):
        c = 4
        g = jax.random.normal(jax.random.PRNGKey(4), (c,) + shape)
        e = jax.random.normal(jax.random.PRNGKey(5), (c,) + shape)
        w = jnp.asarray([0.4, 0.3, 0.2, 0.1])
        k = max(1, shape[0] * shape[1] // 20)
        ks = jnp.asarray([k, k // 2 + 1, 2 * k, 1], jnp.int32)
        _assert_routes_equal(_routes("eftopk", g, w, ks, residuals=e))

    def test_conservation(self):
        """One client at weight 1 on the kernel route: the merged send plus
        the new residual is exactly residual + update — nothing is lost."""
        g = jax.random.normal(jax.random.PRNGKey(6), (1, 30_000))
        e = jax.random.normal(jax.random.PRNGKey(7), (1, 30_000))
        ks = jnp.asarray([1500], jnp.int32)
        agg, new_e = compress_merge_leaf(
            g, jnp.ones((1,)), ks, strategies.get("eftopk"), gamma=1.0,
            overlap_d=1, use_kernel=True, residuals=e)
        np.testing.assert_array_equal(np.asarray(agg + new_e[0]),
                                      np.asarray(e[0] + g[0]))


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [(2, 3, 128, 128, 64),
                                       (1, 2, 256, 256, 32),
                                       (1, 2, 100, 100, 64),
                                       (1, 1, 128, 384, 64)])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_vs_ref(self, shape, dtype):
        b, h, sq, sk, d = shape
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, sq, h, d)).astype(dtype)
        k = jax.random.normal(ks[1], (b, sk, h, d)).astype(dtype)
        v = jax.random.normal(ks[2], (b, sk, h, d)).astype(dtype)
        out = ops.flash_attention(q, k, v, causal=True)
        qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
        vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
        r = ref.flash_attention_ref(qt, kt, vt, True).reshape(
            b, h, sq, d).transpose(0, 2, 1, 3)
        tol = 2e-6 if dtype == jnp.float32 else 2e-3
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(r, np.float32), atol=tol,
                                   rtol=tol)

    def test_matches_model_attend(self):
        """Flash kernel == the model's chunked jnp attention path."""
        from repro.models.attention import attend
        b, s, h, d = 1, 128, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        a = attend(q, k, v, causal=True, chunk=64)
        f = ops.flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64)
        np.testing.assert_allclose(np.asarray(a), np.asarray(f), atol=1e-5,
                                   rtol=1e-5)

    @pytest.mark.parametrize("blocks", [(64, 64), (128, 64), (64, 128)])
    def test_block_shape_invariance(self, blocks):
        bq, bk = blocks
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 256, 2, 32))
        k = jax.random.normal(ks[1], (1, 256, 2, 32))
        v = jax.random.normal(ks[2], (1, 256, 2, 32))
        a = ops.flash_attention(q, k, v, blk_q=bq, blk_k=bk)
        b_ = ops.flash_attention(q, k, v, blk_q=128, blk_k=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5,
                                   rtol=1e-5)
