"""Scan-engine tests: the whole-simulation ``lax.scan`` program must be
bit-exact with the fused per-round engine on the shared seeded rng stream
(accuracy trajectory, comm-time accounting, EF residuals — including the
failure-injection and straggler-renormalization paths), compile exactly once
per simulation, and the fully-traced sampling variant must stand on its own.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import strategies
from repro.core.aggregation import AggregationConfig
from repro.fed import engine
from repro.fed.simulation import (FLSimConfig, _steps_by_client, run_fl,
                                  run_fl_traced)
from repro.ft import FailureInjector, StragglerPolicy
from repro.ft.failures import survivors_traced
from repro.ft.straggler import (arrival_mask_traced,
                                renormalize_coefficients_traced)

FAST = dict(rounds=8, n_train=2000, n_test=600, eval_every=2, seed=3)


def _accs(res):
    return np.array([a for _, a in res.accuracies])


class TestScanParity:
    """engine="scan" and engine="fused" consume the identical host rng
    stream, so their trajectories must match BIT FOR BIT."""

    @pytest.mark.parametrize("strategy,kw", [
        ("fedavg", {}),
        ("topk", dict(cr=0.05)),
        ("eftopk", dict(cr=0.05)),
        ("bcrs", dict(cr=0.05)),
        ("bcrs_opwa", dict(cr=0.05, gamma=5.0)),
    ])
    def test_bitwise_accuracy_and_time_parity(self, strategy, kw):
        acfg = AggregationConfig(strategy=strategy, **kw)
        fused = run_fl(FLSimConfig(**FAST), acfg, engine="fused")
        scan = run_fl(FLSimConfig(**FAST), acfg, engine="scan")
        np.testing.assert_array_equal(_accs(scan), _accs(fused))
        assert scan.times.actual == fused.times.actual
        assert scan.executed_rounds == fused.executed_rounds
        if strategy == "eftopk":
            np.testing.assert_array_equal(scan.final_residuals,
                                          fused.final_residuals)

    @pytest.mark.parametrize("strategy", ["bcrs", "eftopk"])
    def test_failure_injection_parity(self, strategy):
        """Dead clients become zero-weight padded slots in the scan xs; the
        EF residual reset-on-cohort-resize bookkeeping must also line up."""
        acfg = AggregationConfig(strategy=strategy, cr=0.05)
        inj = FailureInjector(p_fail=0.3, seed=1)
        fused = run_fl(FLSimConfig(**FAST), acfg, failure=inj,
                       engine="fused")
        scan = run_fl(FLSimConfig(**FAST), acfg, failure=inj, engine="scan")
        assert scan.executed_rounds == fused.executed_rounds
        np.testing.assert_array_equal(_accs(scan), _accs(fused))
        assert scan.times.actual == fused.times.actual
        if strategy == "eftopk":
            np.testing.assert_array_equal(scan.final_residuals,
                                          fused.final_residuals)

    def test_straggler_renormalization_parity(self):
        """Over-selection + arrival deadline trims the cohort on host; both
        engines must see the same arrived set and renormalized weights."""
        pol = StragglerPolicy(over_selection=0.5)
        acfg = AggregationConfig(strategy="bcrs_opwa", cr=0.05)
        fused = run_fl(FLSimConfig(**FAST), acfg, straggler=pol,
                       engine="fused")
        scan = run_fl(FLSimConfig(**FAST), acfg, straggler=pol,
                      engine="scan")
        np.testing.assert_array_equal(_accs(scan), _accs(fused))
        assert scan.times.actual == fused.times.actual
        assert fused.final_accuracy > 0.35

    def test_overlap_histogram_parity(self):
        acfg = AggregationConfig(strategy="topk", cr=0.05)
        fused = run_fl(FLSimConfig(**FAST), acfg, collect_overlap=True,
                       engine="fused")
        scan = run_fl(FLSimConfig(**FAST), acfg, collect_overlap=True,
                      engine="scan")
        np.testing.assert_array_equal(scan.overlap_hist, fused.overlap_hist)

    def test_legacy_engine_still_matches(self):
        """The engine= spelling routes to the same legacy loop the ``fused``
        bool used to select."""
        acfg = AggregationConfig(strategy="topk", cr=0.05)
        legacy = run_fl(FLSimConfig(**FAST), acfg, engine="legacy")
        scan = run_fl(FLSimConfig(**FAST), acfg, engine="scan")
        np.testing.assert_allclose(_accs(scan), _accs(legacy), atol=1e-3)


class TestScanCompileCount:
    """One scan simulation = exactly ONE trace of the scanned program,
    independent of rounds and cohort size."""

    def _traces(self):
        return sum(engine.TRACE_COUNTS.values())

    def _run(self, rounds, n_clients):
        cfg = FLSimConfig(rounds=rounds, n_clients=n_clients,
                          n_train=2000, n_test=300, eval_every=100, seed=1)
        before = self._traces()
        run_fl(cfg, AggregationConfig(strategy="bcrs_opwa", cr=0.05),
               engine="scan")
        return self._traces() - before

    def test_one_compile_per_simulation(self):
        assert self._run(rounds=3, n_clients=8) == 1
        assert self._run(rounds=12, n_clients=8) == 1

    def test_constant_in_clients(self):
        assert self._run(rounds=4, n_clients=6) == 1
        assert self._run(rounds=4, n_clients=12) == 1


class TestScanTrajectoryMemory:
    """The scanned program snapshots eval rounds into an O(E x n) carried
    buffer — it must NOT emit the model every round (O(rounds x n))."""

    def test_eval_buffer_is_o_evals_not_o_rounds(self):
        from jax.flatten_util import ravel_pytree
        from repro.fed.simulation import mlp_init, mlp_loss
        params = mlp_init(jax.random.PRNGKey(0), dim=8, n_classes=3,
                          hidden=8)
        flat = ravel_pytree(params)[0].astype(jnp.float32)
        n = flat.shape[0]
        r, c, s, b, e = 6, 2, 1, 4, 2
        sim_fn = engine.make_sim_scan(
            mlp_loss, params, lr=0.1,
            acfg=AggregationConfig(strategy="topk", cr=0.5))
        key = jax.random.PRNGKey(1)
        xs = {
            "batches": {
                "x": jax.random.normal(key, (r, c, s, b, 8)),
                "y": jnp.zeros((r, c, s, b), jnp.int32)},
            "step_mask": jnp.ones((r, c, s), bool),
            "active": jnp.ones((r, c), bool),
            "weights": jnp.full((r, c), 0.5, jnp.float32),
            "ks": jnp.full((r, c), 5, jnp.int32),
            "eval_write": jnp.asarray([False, False, True, False, False,
                                       True]),
            "eval_slot": jnp.asarray([0, 0, 0, 0, 0, 1], jnp.int32),
        }
        out = sim_fn(flat, jnp.zeros((0,), jnp.float32),
                     jnp.zeros((e, n), jnp.float32), xs)
        # O(E x n) snapshot buffer; the per-round ys carry no model copy
        assert out["evals"].shape == (e, n)
        assert "flat" not in out["ys"]
        assert all(v.ndim <= 1 for v in out["ys"].values())
        # the last snapshot is the final model (round 5 wrote slot 1)
        np.testing.assert_array_equal(np.asarray(out["evals"][1]),
                                      np.asarray(out["flat"]))


class TestStepCap:
    def test_quantile_cap_tightens_static_shape(self):
        from repro.data import (build_client_datasets, dirichlet_partition,
                                synthetic_classification)
        sim = FLSimConfig(**FAST)            # beta=0.1: extreme skew
        rng = np.random.default_rng(sim.seed)
        x, y = synthetic_classification(sim.n_train + sim.n_test,
                                        sim.n_classes, sim.dim, rng,
                                        noise=sim.noise)
        parts = dirichlet_partition(y[: sim.n_train], sim.n_clients,
                                    sim.beta, rng, min_size=sim.batch_size)
        clients = build_client_datasets(x[: sim.n_train], y[: sim.n_train],
                                        parts)
        full = _steps_by_client(clients, sim)
        capped = _steps_by_client(
            clients, FLSimConfig(**{**FAST, "step_cap_quantile": 0.5}))
        assert capped.max() < full.max()
        assert capped.min() == full.min()    # small clients untouched

    def test_capped_engines_agree_and_learn(self):
        cfg = FLSimConfig(**{**FAST, "step_cap_quantile": 0.5})
        acfg = AggregationConfig(strategy="bcrs_opwa", cr=0.05)
        fused = run_fl(cfg, acfg, engine="fused")
        scan = run_fl(cfg, acfg, engine="scan")
        np.testing.assert_array_equal(_accs(scan), _accs(fused))
        assert scan.final_accuracy > 0.35


class TestActiveMaskSemantics:
    """compress_merge_leaf with padded inactive rows must equal the compacted
    computation — in particular the OPWA overlap counts must not see the
    all-True Top-K masks that zero rows produce."""

    KW = dict(gamma=4.0, overlap_d=1, use_kernel=False)

    def _case(self, strategy, c_act=3, c_pad=2, n=4096, seed=0):
        key = jax.random.PRNGKey(seed)
        u_act = jax.random.normal(key, (c_act, n))
        u = jnp.concatenate([u_act, jnp.zeros((c_pad, n))])
        w_act = jnp.asarray(np.full(c_act, 1.0 / c_act), jnp.float32)
        w = jnp.concatenate([w_act, jnp.zeros((c_pad,))])
        ks = jnp.full((c_act + c_pad,), 128, jnp.int32)
        active = jnp.asarray([True] * c_act + [False] * c_pad)
        return strategies.get(strategy), u, u_act, w, w_act, ks, active

    @pytest.mark.parametrize("strategy", ["fedavg", "topk", "bcrs_opwa"])
    def test_padded_equals_compacted(self, strategy):
        strat, u, u_act, w, w_act, ks, active = self._case(strategy)
        agg_pad, _ = engine.compress_merge_leaf(u, w, ks, strat, active=active,
                                                **self.KW)
        agg_cmp, _ = engine.compress_merge_leaf(u_act, w_act, ks[:3], strat,
                                                **self.KW)
        np.testing.assert_array_equal(np.asarray(agg_pad),
                                      np.asarray(agg_cmp))

    def test_eftopk_inactive_residuals_pass_through(self):
        strat, u, u_act, w, w_act, ks, active = self._case("eftopk")
        res = jax.random.normal(jax.random.PRNGKey(7), u.shape) * 0.1
        agg_pad, r_pad = engine.compress_merge_leaf(
            u, w, ks, strat, residuals=res, active=active, **self.KW)
        agg_cmp, r_cmp = engine.compress_merge_leaf(
            u_act, w_act, ks[:3], strat, residuals=res[:3], **self.KW)
        np.testing.assert_array_equal(np.asarray(agg_pad),
                                      np.asarray(agg_cmp))
        np.testing.assert_array_equal(np.asarray(r_pad[:3]),
                                      np.asarray(r_cmp))
        np.testing.assert_array_equal(np.asarray(r_pad[3:]),
                                      np.asarray(res[3:]))


class TestTracedSampling:
    """run_fl_traced: cohort/survival/arrival draws fully inside the jit."""

    def test_learns_and_compiles_once(self):
        before = sum(engine.TRACE_COUNTS.values())
        res = run_fl_traced(FLSimConfig(**FAST),
                            AggregationConfig(strategy="bcrs_opwa", cr=0.05))
        assert sum(engine.TRACE_COUNTS.values()) - before == 1
        assert res.final_accuracy > 0.4
        assert len(res.executed_rounds) == FAST["rounds"]

    def test_survives_failures_and_stragglers(self):
        res = run_fl_traced(
            FLSimConfig(**FAST),
            AggregationConfig(strategy="eftopk", cr=0.05),
            p_fail=0.3, straggler=StragglerPolicy(over_selection=0.5))
        assert res.final_accuracy > 0.3
        assert res.final_residuals is not None

    def test_survivors_traced_guarantee(self):
        key = jax.random.PRNGKey(0)
        all_alive = survivors_traced(key, 16, 0.0)
        assert bool(all_alive.all())
        # p_fail=1 would kill everyone; exactly one client is revived
        one = survivors_traced(key, 16, 1.0)
        assert int(jnp.sum(one)) == 1

    def test_arrival_mask_traced_picks_fastest(self):
        t = jnp.asarray([3.0, 1.0, jnp.inf, 2.0, 5.0])
        mask = np.asarray(arrival_mask_traced(t, 3))
        np.testing.assert_array_equal(mask, [True, True, False, True, False])

    def test_renormalize_traced_matches_host(self):
        from repro.ft import renormalize_coefficients
        coeffs = np.array([0.4, 0.1, 0.3, 0.2])
        arrived = np.array([True, False, True, False])
        host = renormalize_coefficients(coeffs, arrived)
        traced = np.asarray(renormalize_coefficients_traced(
            jnp.asarray(coeffs, jnp.float32), jnp.asarray(arrived)))
        np.testing.assert_allclose(traced, host, rtol=1e-6)
