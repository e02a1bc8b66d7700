"""Traced-k Pallas megakernel pipeline: bit-exact parity with the jnp
reference path across every strategy, per-client ks pattern, and padding
edge, plus the regression for the old static-CR EF-kernel route.

Everything runs the kernels in interpret mode (this suite executes on CPU);
the jnp route of ``fed.engine.compress_merge_leaf`` is the parity oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyputil import given, settings, st

from repro.core import compression as C
from repro.core import strategies
from repro.fed import engine
from repro.kernels import ops, ref
from repro.kernels.fused_merge import block_lanes as merge_block_lanes
from repro.kernels.fused_merge import fused_merge_pallas
from repro.kernels.threshold_find import block_lanes, threshold_find_pallas

STRATEGIES = ("fedavg", "topk", "eftopk", "bcrs", "bcrs_opwa")


def _bits(x):
    return jax.lax.bitcast_convert_type(
        jnp.abs(jnp.asarray(x, jnp.float32)), jnp.uint32)


def _case(c, n, seed=0, scale=1.0):
    key = jax.random.PRNGKey(seed)
    ku, ke, kw, kk = jax.random.split(key, 4)
    u = jax.random.normal(ku, (c, n)) * scale
    e = jax.random.normal(ke, (c, n)) * 0.3 * scale
    w = jax.random.uniform(kw, (c,)) + 0.1
    w = w / jnp.sum(w)
    ks = jax.random.randint(kk, (c,), 1, n + 1).astype(jnp.int32)
    return u, e, w, ks


class TestThresholdFind:
    @pytest.mark.parametrize("c,n", [(1, 512), (8, 4096), (16, 1024),
                                     (3, 512 * 7)])
    def test_vs_ref(self, c, n):
        u, e, _, ks = _case(c, n, seed=c * 100 + n)
        th = threshold_find_pallas(u, ks.reshape(c, 1), interpret=True)
        np.testing.assert_array_equal(np.asarray(th),
                                      np.asarray(ref.threshold_find_ref(u, ks)))
        # EF variant selects on corrected = residuals + updates
        th_ef = threshold_find_pallas(u, ks.reshape(c, 1), e, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(th_ef), np.asarray(ref.threshold_find_ref(u, ks, e)))

    @pytest.mark.parametrize("k", [1, 2, 511, 512])
    def test_k_edges_exact_mask(self, k):
        u, _, _, _ = _case(4, 512, seed=k)
        ks = jnp.full((4,), k, jnp.int32)
        th = threshold_find_pallas(u, ks.reshape(4, 1), interpret=True)
        mask = _bits(u) >= th
        np.testing.assert_array_equal(
            np.asarray(mask),
            np.asarray(C.topk_compress_batch(u, ks).mask))

    def test_ties_zeros_and_scales(self):
        u, _, _, _ = _case(6, 1024, seed=7)
        u = u.at[0].set(0.0)                       # all-zero row
        u = u.at[1, :500].set(u[1, 0])             # heavy ties
        u = u.at[2].mul(1e-40)                     # subnormal magnitudes
        u = u.at[3].mul(1e30)
        ks = jnp.asarray([5, 500, 13, 1, 1024, 512], jnp.int32)
        th = threshold_find_pallas(u, ks.reshape(6, 1), interpret=True)
        np.testing.assert_array_equal(np.asarray(th),
                                      np.asarray(ref.threshold_find_ref(u, ks)))

    def test_wrapper_pads_ragged_n(self):
        u, e, _, ks = _case(5, 700, seed=3)
        th = ops.topk_thresholds(u, ks)
        np.testing.assert_array_equal(
            np.asarray(th), np.asarray(ref.threshold_find_ref(u, ks))[:, 0])
        th_ef = ops.topk_thresholds(u, ks, residuals=e)
        np.testing.assert_array_equal(
            np.asarray(th_ef),
            np.asarray(ref.threshold_find_ref(u, ks, e))[:, 0])


def _tiled_width(c, size, ef):
    """A width that spans 3 blocks and ends in a partial block and a partial
    folded chunk ("blocks"), or one smaller than one block ("leaf"), from
    the kernel's own block width."""
    w = block_lanes(c, 1 << 40, ef)
    return 2 * w + w // 2 + 384 if size == "blocks" else 3 * 1024 + 384


class TestThresholdFindTiling:
    """The block/chunk tiling of threshold_find, bit for bit against the
    32-halving reference: k=1, k=n, ties straddling the threshold across
    blocks, and an all-zero row, through the kernel and its padding
    wrapper."""

    @pytest.mark.parametrize("mode", ["plain", "ef", "ef_scale"])
    @pytest.mark.parametrize("size", ["blocks", "leaf"])
    @pytest.mark.parametrize("c", [1, 4, 9])
    def test_vs_ref(self, c, size, mode):
        ef = mode != "plain"
        n = _tiled_width(c, size, ef)
        u, e, _, ks = _case(c, n, seed=c + n)
        # 300 tied magnitudes spread over every block, with k inside them
        ties = np.arange(300) * (n // 300)
        u = u.at[0, ties].set(jnp.where(ties % 2 == 0, 5.0, -5.0))
        e = e.at[0, ties].set(0.0)
        ks = ks.at[0].set(150)
        if c > 1:
            u = u.at[1].set(0.0)                   # all-zero row
            e = e.at[1].set(0.0)
            ks = ks.at[2].set(1).at[c - 1].set(n)
        res = e if ef else None
        want = np.asarray(ref.threshold_find_ref(u, ks, res))
        if mode == "ef_scale":
            th, absmax = threshold_find_pallas(u, ks.reshape(c, 1), e,
                                               emit_scale=True)
            np.testing.assert_array_equal(
                np.asarray(absmax),
                np.asarray(jnp.max(jnp.abs(e + u), axis=1, keepdims=True)))
        else:
            th = threshold_find_pallas(u, ks.reshape(c, 1), res)
            # the wrapper pads a ragged width back up to the kernel's
            cut = n - 37
            u_cut, k_cut = u[:, :cut], jnp.minimum(ks, cut)
            r_cut = None if res is None else res[:, :cut]
            np.testing.assert_array_equal(
                np.asarray(ops.topk_thresholds(u_cut, k_cut, r_cut)),
                np.asarray(ref.threshold_find_ref(u_cut, k_cut, r_cut))[:, 0])
        np.testing.assert_array_equal(np.asarray(th), want)


class TestFusedMerge:
    @pytest.mark.parametrize("opwa", [False, True])
    @pytest.mark.parametrize("ef", [False, True])
    @pytest.mark.parametrize("gated", [False, True])
    def test_vs_ref(self, opwa, ef, gated):
        c, n = 7, 2048
        u, e, w, ks = _case(c, n, seed=11)
        active = (jnp.asarray([True] * 5 + [False] * 2) if gated else None)
        if gated:
            u = u * active[:, None]                # padded rows are zero
        th = ref.threshold_find_ref(u, ks, e if ef else None)
        act_f = active.astype(jnp.float32).reshape(c, 1) if gated else None
        out = fused_merge_pallas(u, th, w.reshape(c, 1),
                                 e if ef else None, act_f,
                                 opwa=opwa, gamma=4.0, d=2, interpret=True)
        want = ref.fused_merge_ref(u, th, w, e if ef else None,
                                   active if gated else None,
                                   opwa=opwa, gamma=4.0, d=2)
        if ef:
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          np.asarray(want[0]))
            np.testing.assert_array_equal(np.asarray(out[1]),
                                          np.asarray(want[1]))
        else:
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(want))


def _agg_both(strategy, u, w, ks, residuals=None, active=None, gamma=5.0,
              overlap_d=1):
    """compress_merge_leaf through the kernel route and the jnp reference."""
    strat = strategies.get(strategy)
    return {use_kernel: engine.compress_merge_leaf(
                u, w, ks, strat, gamma=gamma, overlap_d=overlap_d,
                use_kernel=use_kernel, residuals=residuals, active=active)
            for use_kernel in (False, True)}


class TestAggregateUpdatesParity:
    """Kernel-routed compress_merge_leaf on the [C, n] matrix must match the
    traced jnp route BIT FOR BIT for all five strategies with per-client
    traced ks."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_bit_exact(self, strategy):
        u, e, w, ks = _case(9, 3000, seed=21)
        residuals = e if strategy == "eftopk" else None
        out = _agg_both(strategy, u, w, ks, residuals=residuals, gamma=5.0)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        if strategy == "eftopk":
            np.testing.assert_array_equal(np.asarray(out[True][1]),
                                          np.asarray(out[False][1]))

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_bit_exact_with_active_padding(self, strategy):
        c_act, c_pad, n = 5, 3, 2048
        u, e, w, ks = _case(c_act + c_pad, n, seed=33)
        active = jnp.asarray([True] * c_act + [False] * c_pad)
        u = u * active[:, None]
        w = jnp.where(active, w, 0.0)
        residuals = e if strategy == "eftopk" else None
        out = _agg_both(strategy, u, w, ks, residuals=residuals,
                        active=active, gamma=3.0, overlap_d=2)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        if strategy == "eftopk":
            # inactive rows' residuals pass through unchanged on both routes
            np.testing.assert_array_equal(np.asarray(out[True][1]),
                                          np.asarray(out[False][1]))
            np.testing.assert_array_equal(
                np.asarray(out[True][1][c_act:]), np.asarray(e[c_act:]))

    def test_k_extremes_and_ties(self):
        u, e, w, _ = _case(4, 1024, seed=5)
        u = u.at[2].set(0.0)
        u = u.at[3, :700].set(u[3, 0])
        ks = jnp.asarray([1, 1024, 512, 700], jnp.int32)
        for strategy in ("topk", "bcrs_opwa", "eftopk"):
            residuals = e if strategy == "eftopk" else None
            out = _agg_both(strategy, u, w, ks, residuals=residuals)
            np.testing.assert_array_equal(np.asarray(out[True][0]),
                                          np.asarray(out[False][0]))


class TestEFKernelKsRegression:
    """The old ``use_ef_kernel`` route compressed at a STATIC CR, silently
    ignoring varying traced ks. The kernel-on EF route must honor the
    per-client counts exactly."""

    def _varying(self):
        u, e, w, _ = _case(6, 4096, seed=44)
        # strongly varying BCRS-style retained counts — the old route kept
        # k_for_ratio(block, cr)=410 per block for every client
        ks = jnp.asarray([1, 41, 410, 1200, 3000, 4096], jnp.int32)
        return u, e, w, ks

    def test_global_ef_kernel_honors_traced_ks(self):
        u, e, w, ks = self._varying()
        out = _agg_both("eftopk", u, w, ks, residuals=e)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        np.testing.assert_array_equal(np.asarray(out[True][1]),
                                      np.asarray(out[False][1]))

    def test_retained_counts_follow_ks_not_cr(self):
        """Direct symptom check: retained count per client == ks, not the
        static-CR count the old kernel route produced. The survivors are
        the exact zeros of the kernel route's new residuals."""
        u, e, w, ks = self._varying()
        assert strategies.get("eftopk").megakernel
        _, new_res = engine.compress_merge_leaf(
            u, w, ks, strategies.get("eftopk"), gamma=1.0, overlap_d=1,
            use_kernel=True, residuals=e)
        kept = np.sum(np.asarray(new_res) == 0.0, axis=1)
        np.testing.assert_array_equal(kept, np.asarray(ks))


class TestKernelProperty:
    """Hypothesis sweep: random shapes, ks patterns (k=1, k=n, ties at the
    threshold, all-zero rows, inactive masks) — agg and residuals bit-exact
    for every strategy."""

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 10), st.integers(2, 1500), st.integers(0, 10 ** 6),
           st.sampled_from(["topk", "eftopk", "bcrs", "bcrs_opwa"]))
    def test_bit_exact_everywhere(self, c, n, seed, strategy):
        rng = np.random.default_rng(seed)
        u = rng.normal(size=(c, n)).astype(np.float32)
        u *= 10.0 ** rng.integers(-12, 12, size=(c, 1)).astype(np.float32)
        if rng.random() < 0.3:
            u[rng.integers(c)] = 0.0               # all-zero row
        if rng.random() < 0.3 and n > 3:
            r = int(rng.integers(c))
            u[r, : n // 2] = u[r, 0]               # ties at the threshold
        ks = rng.integers(1, n + 1, size=c).astype(np.int32)
        ks[rng.integers(c)] = 1
        ks[rng.integers(c)] = n
        active = None
        if rng.random() < 0.5:
            active = rng.random(c) < 0.7
            active[rng.integers(c)] = True         # >= 1 active row
            u *= active[:, None]
        w = (rng.random(c) + 0.05).astype(np.float32)
        e = (rng.normal(size=(c, n)) * 0.3).astype(np.float32)
        residuals = jnp.asarray(e) if strategy == "eftopk" else None
        out = _agg_both(strategy, jnp.asarray(u), jnp.asarray(w),
                        jnp.asarray(ks),
                        residuals=residuals,
                        active=jnp.asarray(active) if active is not None
                        else None,
                        gamma=float(rng.uniform(1.0, 8.0)),
                        overlap_d=int(rng.integers(1, c + 1)))
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        if strategy == "eftopk":
            np.testing.assert_array_equal(np.asarray(out[True][1]),
                                          np.asarray(out[False][1]))


class TestKernelRoutedScanSim:
    """The kernel-routed scan simulation still compiles exactly once and its
    trajectory is bit-exact with the jnp-routed scan engine."""

    def test_one_compile_and_parity(self):
        from repro.core.aggregation import AggregationConfig
        from repro.fed.simulation import FLSimConfig, run_fl
        cfg = FLSimConfig(rounds=4, n_clients=6, n_train=1200, n_test=300,
                          dim=32, hidden=32, n_classes=5, eval_every=2,
                          seed=2)
        accs = {}
        for use_kernel in (False, True):
            acfg = AggregationConfig(strategy="bcrs_opwa", cr=0.1,
                                     use_kernel=use_kernel)
            before = sum(engine.TRACE_COUNTS.values())
            res = run_fl(cfg, acfg, engine="scan")
            assert sum(engine.TRACE_COUNTS.values()) - before == 1
            accs[use_kernel] = np.array([a for _, a in res.accuracies])
        np.testing.assert_array_equal(accs[True], accs[False])

# --------------------------------------------------------------- codec stage
CODEC_STRATEGIES = ("qtopk", "int4")


def _codec_of(strategy):
    from repro.core import strategies as strat_mod
    return strat_mod.get(strategy).kernel_codec


def _codec_scales(corrected, codec):
    from repro.core.strategies import CODEC_LEVELS, quantization_scale
    absmax = jnp.max(jnp.abs(corrected.astype(jnp.float32)), axis=1,
                     keepdims=True)
    return quantization_scale(absmax, CODEC_LEVELS[codec])


class TestFusedMergeCodec:
    """Tile-level oracle parity for the quantize/dequantize merge stage."""

    @pytest.mark.parametrize("codec", ["int8", "int4"])
    @pytest.mark.parametrize("gated", [False, True])
    @pytest.mark.parametrize("opwa", [False, True])
    def test_vs_ref(self, codec, gated, opwa):
        c, n = 7, 2048
        u, e, w, ks = _case(c, n, seed=61)
        u = u.at[3].set(0.0)                    # all-zero row -> scale 0
        e = e.at[3].set(0.0)
        th = ref.threshold_find_ref(u, ks, e)
        scales = _codec_scales(e + u, codec)
        active = jnp.asarray([1.0] * (c - 2) + [0.0] * 2).reshape(c, 1)
        act = active if gated else None
        out = fused_merge_pallas(u, th, w.reshape(c, 1), e, act,
                                 opwa=opwa, gamma=4.0, d=2, codec=codec,
                                 scales=scales, interpret=True)
        want = ref.fused_merge_ref(u, th, w, e, act, opwa=opwa, gamma=4.0,
                                   d=2, codec=codec, scales=scales)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(want[1]))

    def test_codec_requires_scales_and_residuals(self):
        c, n = 3, 1024
        u, e, w, ks = _case(c, n, seed=62)
        th = ref.threshold_find_ref(u, ks, e)
        with pytest.raises(AssertionError, match="scales"):
            fused_merge_pallas(u, th, w.reshape(c, 1), e, codec="int8",
                               interpret=True)
        with pytest.raises(AssertionError, match="residuals"):
            fused_merge_pallas(u, th, w.reshape(c, 1), codec="int8",
                               scales=_codec_scales(e + u, "int8"),
                               interpret=True)


class TestFusedMergeRaggedWidth:
    """The merge kernel zero-pads ragged widths internally (the old hard
    ``n % TILE_N == 0`` assert) and slices the outputs back."""

    @pytest.mark.parametrize("n", [4, 10, 1500, 2050])
    @pytest.mark.parametrize("codec", ["none", "int8"])
    def test_vs_ref_even_ragged(self, n, codec):
        # even widths: the jnp reference einsum and the kernel's tile-padded
        # dot accumulate identically (see DESIGN.md §10 on the XLA:CPU gemv
        # tail of small ODD widths — a pre-existing artifact shared by every
        # kernel strategy, orthogonal to padding and codecs)
        c = 5
        u, e, w, ks = _case(c, n, seed=63 + n)
        th = ref.threshold_find_ref(u, ks, e)
        scales = _codec_scales(e + u, "int8") if codec != "none" else None
        out = fused_merge_pallas(u, th, w.reshape(c, 1), e, codec=codec,
                                 scales=scales, interpret=True)
        want = ref.fused_merge_ref(u, th, w, e, codec=codec, scales=scales)
        assert out[0].shape == (1, n) and out[1].shape == (c, n)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(want[1]))

    def test_odd_width_residuals_exact(self):
        # literally-odd width: the elementwise outputs (residuals) are still
        # bit-exact; the merged aggregate is only pinned to a few ULP
        # because the reference's [C, n] gemv uses a different tail
        # accumulation than the kernel's tile-aligned dot at small odd n
        c, n = 5, 17
        u, e, w, ks = _case(c, n, seed=64)
        th = ref.threshold_find_ref(u, ks, e)
        out = fused_merge_pallas(u, th, w.reshape(c, 1), e, interpret=True)
        want = ref.fused_merge_ref(u, th, w, e)
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(want[1]))
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[0]),
                                   rtol=1e-6, atol=0)


class TestFusedMergeTiling:
    """The block/chunk tiling of fused_merge, bit for bit against the
    oracle: widths over several blocks that end in a partial block and a
    partial chunk, or inside one block; ties straddling the threshold in
    every block, an all-zero row, and inactive padded rows."""

    @pytest.mark.parametrize("mode", ["plain", "opwa", "ef", "ef_int8"])
    @pytest.mark.parametrize("size", ["blocks", "leaf"])
    @pytest.mark.parametrize("c", [1, 4, 9])
    def test_vs_ref(self, c, size, mode):
        ef = mode.startswith("ef")
        w_block = merge_block_lanes(c, 1 << 40, ef)
        n = (2 * w_block + w_block // 2 + 384 if size == "blocks"
             else 3 * 1024 + 384)
        assert (n > 2 * w_block) == (size == "blocks")
        u, e, w, ks = _case(c, n, seed=c + n)
        # 300 tied magnitudes spread over every block, with k inside them
        ties = np.arange(300) * (n // 300)
        u = u.at[0, ties].set(jnp.where(ties % 2 == 0, 5.0, -5.0))
        e = e.at[0, ties].set(0.0)
        ks = ks.at[0].set(150)
        active = None
        if c > 1:
            u = u.at[1].set(0.0)                   # all-zero row
            e = e.at[1].set(0.0)
            if mode == "opwa":                     # a padded, inactive row
                active = jnp.ones((c, 1)).at[c - 1].set(0.0)
                u = u.at[c - 1].set(0.0)
        res = e if ef else None
        codec = "int8" if mode == "ef_int8" else "none"
        scales = _codec_scales(e + u, codec) if codec != "none" else None
        th = ref.threshold_find_ref(u, ks, res)
        kw = dict(opwa=mode != "plain", gamma=4.0, d=2, codec=codec,
                  scales=scales)
        out = fused_merge_pallas(u, th, w.reshape(c, 1), res, active,
                                 interpret=True, **kw)
        want = ref.fused_merge_ref(u, th, w, res, active, **kw)
        out, want = (out, want) if ef else ((out,), (want,))
        for got, expect in zip(out, want):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(expect))


class TestCodecScaleProvenance:
    """threshold_find's emitted absmax IS the jnp codec's scale source: for
    Top-K (ties kept, k >= 1) the survivors' absmax equals the row absmax,
    and fp max is exact, so the tile-accumulated max matches ``jnp.max``
    bit for bit — including all-zero rows (scale 0) and tied rows."""

    def test_absmax_matches_row_max(self):
        c, n = 6, 512 * 5
        u, e, _, ks = _case(c, n, seed=65)
        u = u.at[2].set(0.0)
        e = e.at[2].set(0.0)
        u = u.at[4, :600].set(u[4, 0])          # ties
        th, absmax = threshold_find_pallas(u, ks.reshape(c, 1), e,
                                           emit_scale=True, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(th),
            np.asarray(threshold_find_pallas(u, ks.reshape(c, 1), e,
                                             interpret=True)))
        want = jnp.max(jnp.abs(e + u), axis=1, keepdims=True)
        np.testing.assert_array_equal(np.asarray(absmax), np.asarray(want))

    def test_survivor_absmax_equals_row_absmax(self):
        u, e, _, ks = _case(8, 2048, seed=66)
        corrected = e + u
        comp = jax.vmap(C.topk_compress_dynamic)(corrected, ks)
        surv = jnp.max(jnp.abs(comp.values), axis=1)
        np.testing.assert_array_equal(
            np.asarray(surv), np.asarray(jnp.max(jnp.abs(corrected), axis=1)))


class TestCodecKernelParity:
    """End-to-end compress_merge_leaf: the codec megakernel route must match
    the jnp value_codec path bit for bit — aggregate AND EF residuals."""

    @pytest.mark.parametrize("strategy", CODEC_STRATEGIES)
    def test_bit_exact(self, strategy):
        u, e, w, ks = _case(9, 3000, seed=67)
        out = _agg_both(strategy, u, w, ks, residuals=e, gamma=5.0)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        np.testing.assert_array_equal(np.asarray(out[True][1]),
                                      np.asarray(out[False][1]))

    @pytest.mark.parametrize("strategy", CODEC_STRATEGIES)
    def test_bit_exact_with_active_padding(self, strategy):
        c_act, c_pad, n = 5, 3, 2048
        u, e, w, ks = _case(c_act + c_pad, n, seed=68)
        active = jnp.asarray([True] * c_act + [False] * c_pad)
        u = u * active[:, None]
        w = jnp.where(active, w, 0.0)
        out = _agg_both(strategy, u, w, ks, residuals=e, active=active,
                        gamma=3.0, overlap_d=2)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        np.testing.assert_array_equal(np.asarray(out[True][1]),
                                      np.asarray(out[False][1]))
        # inactive rows' residuals pass through unchanged on both routes
        np.testing.assert_array_equal(np.asarray(out[True][1][c_act:]),
                                      np.asarray(e[c_act:]))

    @pytest.mark.parametrize("strategy", CODEC_STRATEGIES)
    def test_k_extremes_ties_and_zero_rows(self, strategy):
        u, e, w, _ = _case(4, 1024, seed=69)
        u = u.at[2].set(0.0)                    # zero row: codec scale 0
        e = e.at[2].set(0.0)
        u = u.at[3, :700].set(u[3, 0])          # ties at the threshold
        ks = jnp.asarray([1, 1024, 512, 700], jnp.int32)
        out = _agg_both(strategy, u, w, ks, residuals=e)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        np.testing.assert_array_equal(np.asarray(out[True][1]),
                                      np.asarray(out[False][1]))
        # the zero row's residual stays exactly zero on both routes
        assert not np.any(np.asarray(out[True][1][2]))


class TestKernelPropertyCodec:
    """Hypothesis sweep for the codec strategies: random shapes, per-client
    ks, ties, zero rows, inactive masks — agg and residuals bit-exact.
    Widths are even (see DESIGN.md §10: XLA:CPU's gemv accumulates the tail
    of small odd widths differently between the reference's [C, n] einsum
    and the kernel's tile-aligned dot — for every kernel strategy, codec or
    not — so odd widths are pinned at tile level, not end-to-end)."""

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 10), st.integers(1, 750), st.integers(0, 10 ** 6),
           st.sampled_from(CODEC_STRATEGIES))
    def test_bit_exact_everywhere(self, c, half_n, seed, strategy):
        n = 2 * half_n
        rng = np.random.default_rng(seed)
        u = rng.normal(size=(c, n)).astype(np.float32)
        u *= 10.0 ** rng.integers(-12, 12, size=(c, 1)).astype(np.float32)
        if rng.random() < 0.3:
            u[rng.integers(c)] = 0.0               # all-zero row
        if rng.random() < 0.3 and n > 3:
            r = int(rng.integers(c))
            u[r, : n // 2] = u[r, 0]               # ties at the threshold
        ks = rng.integers(1, n + 1, size=c).astype(np.int32)
        ks[rng.integers(c)] = 1
        ks[rng.integers(c)] = n
        e = (rng.normal(size=(c, n)) * 0.3).astype(np.float32)
        active = None
        if rng.random() < 0.5:
            active = rng.random(c) < 0.7
            active[rng.integers(c)] = True         # >= 1 active row
            u *= active[:, None]
            e = np.where(active[:, None], e, e * 0.5)
        w = (rng.random(c) + 0.05).astype(np.float32)
        out = _agg_both(strategy, jnp.asarray(u), jnp.asarray(w),
                        jnp.asarray(ks), residuals=jnp.asarray(e),
                        active=jnp.asarray(active) if active is not None
                        else None)
        np.testing.assert_array_equal(np.asarray(out[True][0]),
                                      np.asarray(out[False][0]))
        np.testing.assert_array_equal(np.asarray(out[True][1]),
                                      np.asarray(out[False][1]))


class TestCodecKernelRoutedScanSim:
    """The codec kernel route through the scanned driver: one compile, and
    the whole trajectory bit-exact with the jnp-routed scan."""

    def test_one_compile_and_parity(self):
        from repro.core.aggregation import AggregationConfig
        from repro.fed.simulation import FLSimConfig, run_fl
        cfg = FLSimConfig(rounds=4, n_clients=6, n_train=1200, n_test=300,
                          dim=32, hidden=32, n_classes=5, eval_every=2,
                          seed=3)
        accs = {}
        for use_kernel in (False, True):
            acfg = AggregationConfig(strategy="qtopk", cr=0.1,
                                     use_kernel=use_kernel)
            before = sum(engine.TRACE_COUNTS.values())
            res = run_fl(cfg, acfg, engine="scan")
            assert sum(engine.TRACE_COUNTS.values()) - before == 1
            accs[use_kernel] = np.array([a for _, a in res.accuracies])
        np.testing.assert_array_equal(accs[True], accs[False])
