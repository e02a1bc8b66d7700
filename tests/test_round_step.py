"""Fused round program tests: parity with the legacy per-client loop,
EF bit-compatibility, O(1) compile behavior, traced-k correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.core.aggregation import (AggregationConfig, compress_clients,
                                    compress_clients_loop, round_schedule)
from repro.fed import round_step
from repro.fed.simulation import FLSimConfig, run_fl

FAST = dict(rounds=8, n_train=2000, n_test=600, eval_every=2, seed=3)


def _accs(res):
    return np.array([a for _, a in res.accuracies])


class TestFusedParity:
    """Same seed -> fused and legacy engines see identical data streams and
    schedules; accuracies must match within 1e-3 (observed: bit-exact)."""

    @pytest.mark.parametrize("strategy,kw", [
        ("fedavg", {}),
        ("topk", dict(cr=0.05)),
        ("eftopk", dict(cr=0.05)),
        ("bcrs", dict(cr=0.05)),
        ("bcrs_opwa", dict(cr=0.05, gamma=5.0)),
    ])
    def test_accuracy_parity(self, strategy, kw):
        acfg = AggregationConfig(strategy=strategy, **kw)
        legacy = run_fl(FLSimConfig(**FAST), acfg, fused=False)
        fused = run_fl(FLSimConfig(**FAST), acfg, fused=True)
        np.testing.assert_allclose(_accs(fused), _accs(legacy), atol=1e-3)
        # host-side schedules are shared -> identical comm-time accounting
        assert fused.times.actual == pytest.approx(legacy.times.actual,
                                                   rel=1e-9)

    def test_overlap_histogram_parity(self):
        acfg = AggregationConfig(strategy="topk", cr=0.05)
        legacy = run_fl(FLSimConfig(**FAST), acfg, collect_overlap=True,
                        fused=False)
        fused = run_fl(FLSimConfig(**FAST), acfg, collect_overlap=True,
                       fused=True)
        np.testing.assert_array_equal(fused.overlap_hist, legacy.overlap_hist)

    def test_overlap_histogram_parity_fedavg(self):
        """fedavg has no schedule CRs; the overlap instrumentation must
        fall back to acfg.cr in both engines (not the all-ones schedule)."""
        acfg = AggregationConfig(strategy="fedavg", cr=0.05)
        legacy = run_fl(FLSimConfig(**FAST), acfg, collect_overlap=True,
                        fused=False)
        fused = run_fl(FLSimConfig(**FAST), acfg, collect_overlap=True,
                       fused=True)
        np.testing.assert_array_equal(fused.overlap_hist, legacy.overlap_hist)

    def test_failure_injection_fused(self):
        from repro.ft import FailureInjector
        acfg = AggregationConfig(strategy="bcrs", cr=0.05)
        inj = FailureInjector(p_fail=0.3, seed=1)
        res = run_fl(FLSimConfig(**FAST), acfg, failure=inj, fused=True)
        assert res.final_accuracy > 0.35


class TestTimeToAccuracy:
    def _result(self, executed, accs):
        from repro.core.cost_model import RoundTime, TimeAccumulator
        from repro.fed.simulation import FLSimResult
        times = TimeAccumulator()
        for _ in executed:
            times.add(RoundTime(actual=1.0, max=1.0, min=1.0))
        return FLSimResult(accuracies=accs, times=times,
                           executed_rounds=list(executed))

    def test_includes_hitting_round(self):
        res = self._result([0, 1, 2, 3], [(0, 0.1), (2, 0.5)])
        # rounds 0,1,2 executed by the time accuracy hits at round 2
        assert res.time_to_accuracy(0.5) == pytest.approx(3.0)

    def test_skipped_rounds_not_counted(self):
        # round 2 skipped by failure injection: no time entry for it
        res = self._result([0, 1, 3, 4], [(0, 0.1), (4, 0.5)])
        assert res.time_to_accuracy(0.5) == pytest.approx(4.0)
        assert res.time_to_accuracy(0.9) is None


class TestEFBitCompatibility:
    """The vectorized traced-k EF path must reproduce the legacy per-client
    static-CR loop bit for bit (values, masks, and residuals)."""

    def _updates(self, k=4, n=5000, seed=0):
        key = jax.random.PRNGKey(seed)
        ku, kr = jax.random.split(key)
        return (jax.random.normal(ku, (k, n)),
                jax.random.normal(kr, (k, n)) * 0.1)

    def test_ef_residuals_bitwise(self):
        updates, residuals = self._updates()
        crs = np.array([0.01, 0.1, 0.5, 1.0])
        acfg = AggregationConfig(strategy="eftopk")
        v1, m1, r1 = compress_clients_loop(updates, crs, acfg, residuals)
        v2, m2, r2 = compress_clients(updates, crs, acfg, residuals)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))

    def test_plain_compress_bitwise(self):
        updates, _ = self._updates(seed=5)
        crs = np.array([0.02, 0.3, 0.9, 1.0])
        acfg = AggregationConfig(strategy="bcrs")
        v1, m1, _ = compress_clients_loop(updates, crs, acfg)
        v2, m2, _ = compress_clients(updates, crs, acfg)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


class TestCompileCount:
    """One fused simulation = O(1) traces of the round program, independent
    of rounds and cohort size (trace-cache inspection via TRACE_COUNTS)."""

    def _traces(self):
        return sum(round_step.TRACE_COUNTS.values())

    def _run(self, rounds, n_clients):
        cfg = FLSimConfig(rounds=rounds, n_clients=n_clients,
                          n_train=2000, n_test=300, eval_every=100, seed=1)
        before = self._traces()
        run_fl(cfg, AggregationConfig(strategy="bcrs_opwa", cr=0.05),
               fused=True)
        return self._traces() - before

    def test_constant_in_rounds(self):
        t_short = self._run(rounds=3, n_clients=8)
        t_long = self._run(rounds=12, n_clients=8)
        assert t_short == t_long == 1

    def test_constant_in_clients(self):
        t_small = self._run(rounds=4, n_clients=6)
        t_big = self._run(rounds=4, n_clients=12)
        assert t_small == t_big == 1

    def test_overlap_variant_adds_one_trace(self):
        cfg = FLSimConfig(rounds=6, n_clients=8, n_train=2000, n_test=300,
                          eval_every=100, seed=2)
        before = self._traces()
        run_fl(cfg, AggregationConfig(strategy="topk", cr=0.1),
               collect_overlap=True, fused=True)
        assert self._traces() - before == 2  # plain step + overlap variant


class TestDynamicVsStatic:
    """Deterministic (non-hypothesis) equivalence sweep: the integer-bit
    bisection must reproduce exact static top-k masks, including the
    CR -> 1 (k = n) edge where a value-space bisection loses exactness."""

    @pytest.mark.parametrize("n,k", [(16, 1), (100, 7), (1000, 100),
                                     (1000, 999), (1000, 1000),
                                     (4096, 4096), (5000, 1)])
    def test_mask_equals_static(self, n, k, seed=0):
        u = jax.random.normal(jax.random.PRNGKey(seed + n + k), (n,))
        dyn = C.topk_compress_dynamic(u, jnp.int32(k))
        mag = jnp.abs(u)
        thresh = jax.lax.top_k(mag, k)[0][-1]
        np.testing.assert_array_equal(np.asarray(dyn.mask),
                                      np.asarray(mag >= thresh))
        np.testing.assert_array_equal(np.asarray(dyn.values),
                                      np.asarray(jnp.where(mag >= thresh,
                                                           u, 0)))

    def test_ties_kept(self):
        u = jnp.asarray([1.0, -1.0, 1.0, 0.5, 2.0])
        dyn = C.topk_compress_dynamic(u, jnp.int32(2))
        # threshold is 1.0; all three tied magnitudes stay (static semantics)
        np.testing.assert_array_equal(np.asarray(dyn.mask),
                                      [True, True, True, False, True])

    def test_batch_matches_per_row(self):
        u = jax.random.normal(jax.random.PRNGKey(9), (5, 777))
        ks = jnp.asarray([1, 10, 100, 776, 777], jnp.int32)
        batch = C.topk_compress_batch(u, ks)
        for i in range(5):
            one = C.topk_compress_dynamic(u[i], ks[i])
            np.testing.assert_array_equal(np.asarray(batch.mask[i]),
                                          np.asarray(one.mask))


class TestRoundScheduleHelper:
    def test_fedavg_has_no_crs(self):
        crs, w, info = round_schedule(AggregationConfig(strategy="fedavg"),
                                      4, np.full(4, 0.25))
        assert "crs" not in info          # time accounting falls back to CR=1
        np.testing.assert_allclose(crs, 1.0)

    def test_bcrs_matches_make_schedule(self):
        from repro.core import bcrs as bcrs_mod
        rng = np.random.default_rng(0)
        from repro.core.cost_model import sample_links
        links = sample_links(4, rng)
        fr = np.full(4, 0.25)
        acfg = AggregationConfig(strategy="bcrs", cr=0.05, alpha=1.0)
        crs, w, info = round_schedule(acfg, 4, fr, links, v_bytes=1e6)
        sched = bcrs_mod.make_schedule(links, fr, 1e6, 0.05, 1.0)
        np.testing.assert_allclose(crs, sched.crs)
        np.testing.assert_allclose(w, sched.coefficients)
        assert info["t_bench"] == sched.t_bench
