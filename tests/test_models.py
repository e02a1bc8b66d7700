"""Model correctness: GLA chunked-vs-recurrent equivalence, prefill/decode
consistency, attention masks, MoE routing invariants (the dropless expert
layer against the reference: tests/test_deepseek_v2_lite.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models import attention as attn
from repro.models import gla
from repro.models import moe as moe_mod


class TestGLA:
    @pytest.mark.parametrize("inclusive", [False, True])
    @pytest.mark.parametrize("scalar", [False, True])
    def test_chunked_matches_recurrence(self, inclusive, scalar):
        b, h, t, dk, dv = 2, 3, 64, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        r = jax.random.normal(ks[0], (b, h, t, dk))
        k = jax.random.normal(ks[1], (b, h, t, dk))
        v = jax.random.normal(ks[2], (b, h, t, dv))
        gshape = (b, h, t) if scalar else (b, h, t, dk)
        g = -jax.nn.softplus(jax.random.normal(ks[3], gshape))
        u = None if inclusive else jax.random.normal(ks[4], (h, dk)) * 0.1
        o_c, s_c = gla.chunked_gla(r, k, v, g, u=u, chunk=16,
                                   inclusive=inclusive)
        o_r, s_r = gla.reference_recurrence(r, k, v, g, u=u,
                                            inclusive=inclusive)
        np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r),
                                   rtol=2e-4, atol=2e-4)

    def test_chunk_size_invariance(self):
        b, h, t, d = 1, 2, 96, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        r, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks[:3])
        g = -jax.nn.softplus(jax.random.normal(ks[3], (b, h, t, d)))
        o16, _ = gla.chunked_gla(r, k, v, g, chunk=16)
        o32, _ = gla.chunked_gla(r, k, v, g, chunk=32)
        np.testing.assert_allclose(np.asarray(o16), np.asarray(o32),
                                   rtol=2e-4, atol=2e-4)

    def test_strong_decay_stability(self):
        """Aggressive decay (rwkv-style) must not produce inf/nan."""
        b, h, t, d = 1, 2, 128, 8
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        r, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks[:3])
        g = jnp.full((b, h, t, d), -5.0)  # decay ~ exp(-5) per step
        o, s = gla.chunked_gla(r, k, v, g, chunk=32)
        assert np.isfinite(np.asarray(o)).all()
        assert np.isfinite(np.asarray(s)).all()

    def test_decode_step_matches_recurrence(self):
        b, h, t, d = 1, 2, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        r, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks[:3])
        g = -jax.nn.softplus(jax.random.normal(ks[3], (b, h, t, d)))
        o_ref, s_ref = gla.reference_recurrence(r, k, v, g)
        s = jnp.zeros((b, h, d, d))
        outs = []
        for i in range(t):
            o, s = gla.gla_decode(r[:, :, i], k[:, :, i], v[:, :, i],
                                  g[:, :, i], s)
            outs.append(o)
        np.testing.assert_allclose(np.asarray(jnp.stack(outs, 2)),
                                   np.asarray(o_ref), rtol=1e-5, atol=1e-5)


class TestAttention:
    def test_causal_mask(self):
        """Future tokens must not influence earlier outputs."""
        b, s, h, d = 1, 16, 2, 8
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)
        out1 = attn.attend(q, k, v, causal=True)
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(-99.0)
        out2 = attn.attend(q, k2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                                   np.asarray(out2[:, :-1]), rtol=1e-5)

    def test_chunked_equals_unchunked(self):
        b, s, h, d = 2, 256, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)
        a = attn.attend(q, k, v, causal=True, chunk=64)
        b_ = attn.attend(q, k, v, causal=True, chunk=256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=1e-5)

    def test_nondivisible_chunk_padding(self):
        b, s, h, d = 1, 100, 2, 8
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)
        a = attn.attend(q, k, v, causal=True, chunk=32)
        b_ = attn.attend(q, k, v, causal=True, chunk=100)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=1e-5)

    def test_window_subset_of_causal(self):
        b, s, h, d = 1, 64, 2, 8
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)
        w = attn.attend(q, k, v, causal=True, window=8)
        # windowed output at position i only depends on keys in (i-8, i]
        k2 = k.at[:, 0].set(50.0)
        w2 = attn.attend(q, k2, v, causal=True, window=8)
        np.testing.assert_allclose(np.asarray(w[:, 16:]),
                                   np.asarray(w2[:, 16:]), rtol=1e-5)

    def test_gqa_group_broadcast(self):
        """GQA with kv=1 equals MQA: every head group sees the same kv."""
        b, s, h, d = 1, 32, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, 1, d))
        v = jax.random.normal(ks[2], (b, s, 1, d))
        out = attn.attend(q, k, v, causal=True)
        kb = jnp.broadcast_to(k, (b, s, h, d))
        vb = jnp.broadcast_to(v, (b, s, h, d))
        out_b = attn.attend(q, kb, vb, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_b),
                                   rtol=1e-5, atol=1e-6)

    def test_decode_matches_full(self):
        """decode_attend over a filled cache == last row of full attention."""
        b, s, h, d = 2, 24, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, 2, d))
        v = jax.random.normal(ks[2], (b, s, 2, d))
        full = attn.attend(q, k, v, causal=True)
        dec = attn.decode_attend(q[:, -1], k, v, jnp.asarray(s - 1))
        np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                                   rtol=1e-4, atol=1e-5)


class TestMoE:
    def _setup(self, n_experts=8, top_k=2, d=16, dexp=32):
        mo = MoEConfig(n_experts=n_experts, top_k=top_k, d_expert=dexp,
                       n_shared=1, d_shared=dexp)
        p = moe_mod.init_moe(jax.random.PRNGKey(0), d, mo, jnp.float32)
        return mo, p

    def test_output_shape_and_finite(self):
        mo, p = self._setup()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
        out, aux = moe_mod.apply_moe(p, x, mo=mo)
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()
        assert float(aux) > 0

    def test_aux_loss_balanced_lower(self):
        """Uniform router (zero weights) -> aux close to 1 (its minimum)."""
        mo, p = self._setup(n_experts=16)
        p = dict(p, router=jnp.zeros_like(p["router"]))
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 16))
        _, aux = moe_mod.apply_moe(p, x, mo=mo)
        assert float(aux) < 1.5
