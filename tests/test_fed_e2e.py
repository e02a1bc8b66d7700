"""End-to-end FL behaviour tests: convergence, paper-claim directionality,
fault tolerance, mesh-parallel round equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.aggregation import AggregationConfig
from repro.fed.mesh_round import make_fl_round_step
from repro.fed.simulation import FLSimConfig, run_fl
from repro.ft import FailureInjector
from repro.models import Model

FAST = dict(rounds=12, n_train=2000, n_test=600, eval_every=2, seed=3)


class TestSimulation:
    def test_fedavg_learns(self):
        res = run_fl(FLSimConfig(**FAST),
                     AggregationConfig(strategy="fedavg"))
        assert res.final_accuracy > 0.5

    def test_topk_learns_slower_at_high_compression(self):
        dense = run_fl(FLSimConfig(**FAST),
                       AggregationConfig(strategy="fedavg"))
        topk = run_fl(FLSimConfig(**FAST),
                      AggregationConfig(strategy="topk", cr=0.01))
        assert topk.final_accuracy <= dense.final_accuracy + 0.02

    def test_bcrs_not_worse_than_topk(self):
        """Paper claim: BCRS >= TopK at the same CR* (more info, same time)."""
        topk = run_fl(FLSimConfig(**FAST),
                      AggregationConfig(strategy="topk", cr=0.01))
        bcrs = run_fl(FLSimConfig(**FAST),
                      AggregationConfig(strategy="bcrs", cr=0.01, alpha=1.0))
        assert bcrs.final_accuracy >= topk.final_accuracy - 0.03

    def test_bcrs_comm_time_equals_topk_benchmark(self):
        topk = run_fl(FLSimConfig(**FAST),
                      AggregationConfig(strategy="topk", cr=0.01))
        bcrs = run_fl(FLSimConfig(**FAST),
                      AggregationConfig(strategy="bcrs", cr=0.01))
        assert bcrs.times.actual == pytest.approx(topk.times.actual, rel=1e-6)

    def test_fedavg_much_slower_comm(self):
        dense = run_fl(FLSimConfig(**FAST),
                       AggregationConfig(strategy="fedavg"))
        comp = run_fl(FLSimConfig(**FAST),
                      AggregationConfig(strategy="topk", cr=0.01))
        assert dense.times.actual > 5 * comp.times.actual

    def test_survives_client_failures(self):
        inj = FailureInjector(p_fail=0.3, seed=1)
        res = run_fl(FLSimConfig(**FAST),
                     AggregationConfig(strategy="bcrs", cr=0.05),
                     failure=inj)
        assert res.final_accuracy > 0.35  # still learns under 30% dropout


class TestMeshRound:
    def _setup(self):
        cfg = get_config("stablelm-1.6b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        c, steps, bs, s = 4, 2, 2, 32
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size, (c, steps, bs, s + 1))
        batches = {"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
                   "labels": jnp.asarray(toks[..., 1:], jnp.int32)}
        return cfg, model, params, batches, c

    def test_round_changes_params_and_loss_finite(self):
        cfg, model, params, batches, c = self._setup()
        fn = jax.jit(make_fl_round_step(model, lr_local=1e-2))
        coeffs = jnp.full((c,), 1.0 / c)
        crs = jnp.full((c,), 0.1)
        new_params, loss = fn(params, batches, coeffs, crs)
        assert np.isfinite(float(loss))
        diffs = jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            params, new_params))
        assert max(diffs) > 0

    def test_cr_one_uncompressed_matches_dense_round(self):
        """CR=1 keeps every parameter -> compressed round == dense round."""
        cfg, model, params, batches, c = self._setup()
        comp_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                             compress=True, gamma=1.0))
        dense_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                              compress=False))
        coeffs = jnp.full((c,), 1.0 / c)
        p1, _ = comp_fn(params, batches, coeffs, jnp.ones((c,)))
        p2, _ = dense_fn(params, batches, coeffs, jnp.ones((c,)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-4, atol=5e-5)

    def test_higher_cr_closer_to_dense(self):
        cfg, model, params, batches, c = self._setup()
        fn = jax.jit(make_fl_round_step(model, lr_local=1e-2, gamma=1.0))
        dense_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                              compress=False))
        coeffs = jnp.full((c,), 1.0 / c)
        pd, _ = dense_fn(params, batches, coeffs, jnp.ones((c,)))
        flat = lambda t: jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in jax.tree.leaves(t)])
        errs = []
        for cr in [0.01, 0.3]:
            pc, _ = fn(params, batches, coeffs, jnp.full((c,), cr))
            errs.append(float(jnp.linalg.norm(flat(pc) - flat(pd))))
        assert errs[1] < errs[0]


class TestMeshRoundStepParity:
    """mesh_round and round_step now share ONE compression substrate
    (repro.fed.engine backed by core.compression.topk_compress_dynamic).
    On a tiny 2-leaf model the two engines must agree."""

    C, B, S, DIM, OUT = 3, 8, 2, 16, 4

    class _TwoLeafModel:
        """Linear model with two leaves (w [dim,out], b [out])."""

        @staticmethod
        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"] + params["b"]
            err = pred - batch["t"]
            return jnp.mean(err * err), pred

    def _setup(self, seed=0):
        rng = np.random.default_rng(seed)
        params = {"w": jnp.asarray(rng.normal(size=(self.DIM, self.OUT)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(self.OUT,)),
                                   jnp.float32)}
        batches = {"x": jnp.asarray(rng.normal(
                       size=(self.C, self.S, self.B, self.DIM)), jnp.float32),
                   "t": jnp.asarray(rng.normal(
                       size=(self.C, self.S, self.B, self.OUT)), jnp.float32)}
        coeffs = jnp.asarray(rng.dirichlet(np.ones(self.C)), jnp.float32)
        return params, batches, coeffs

    def test_cr_one_matches_fused_round_step(self):
        """At CR=1 both engines keep every parameter, so the per-leaf mesh
        selection and the whole-model-flatten fused selection coincide and
        the server updates must match."""
        from repro.core.aggregation import AggregationConfig
        from repro.core.compression import flatten_tree
        from repro.fed.mesh_round import make_fl_round_step
        from repro.fed.round_step import make_round_step

        model = self._TwoLeafModel()
        params, batches, coeffs = self._setup()
        mesh_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                             gamma=1.0))
        p_mesh, _ = mesh_fn(params, batches, coeffs,
                            jnp.ones((self.C,)))

        acfg = AggregationConfig(strategy="bcrs", cr=1.0)
        step = make_round_step(model.loss_fn, params, lr=1e-2, acfg=acfg)
        flat, unravel = flatten_tree(params)
        n = flat.shape[0]
        mask = jnp.ones((self.C, self.S), bool)
        ks = jnp.full((self.C,), n, jnp.int32)
        out = step(flat.astype(jnp.float32), None, batches, mask, coeffs,
                   ks, ks)
        p_fused = unravel(out["flat"])
        for a, b in zip(jax.tree.leaves(p_mesh), jax.tree.leaves(p_fused)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_compressed_leaf_matches_substrate_reference(self):
        """The mesh round's per-leaf path must equal the shared substrate
        computed directly: vmapped topk_compress_dynamic + OPWA merge."""
        from repro.core.compression import topk_compress_dynamic
        from repro.core.opwa import opwa_aggregate
        from repro.fed.client import make_local_trainer
        from repro.fed.mesh_round import make_fl_round_step

        model = self._TwoLeafModel()
        params, batches, coeffs = self._setup(seed=5)
        gamma, cr = 3.0, 0.25
        mesh_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                             gamma=gamma))
        p_mesh, _ = mesh_fn(params, batches, coeffs,
                            jnp.full((self.C,), cr))

        local_train = make_local_trainer(model.loss_fn, 1e-2)
        deltas, _ = jax.vmap(local_train, in_axes=(None, 0))(params, batches)
        for name in ("w", "b"):
            dl = deltas[name].astype(jnp.float32)
            leaf_n = dl[0].size
            ks = jnp.clip(jnp.round(jnp.full((self.C,), cr) * leaf_n)
                          .astype(jnp.int32), 1, leaf_n)
            comp = jax.vmap(topk_compress_dynamic)(dl, ks)
            agg = opwa_aggregate(comp.values, comp.mask, coeffs, gamma,
                                 d=1)
            expect = params[name].astype(jnp.float32) - agg
            np.testing.assert_allclose(np.asarray(p_mesh[name]),
                                       np.asarray(expect),
                                       rtol=1e-6, atol=1e-7)

    def test_cr_one_exactness_per_leaf(self):
        """The deleted float-space bisection lost coordinates at CR=1; the
        shared integer-bit bisection must keep EVERY parameter (compressed
        round == dense round bitwise)."""
        from repro.fed.mesh_round import make_fl_round_step

        model = self._TwoLeafModel()
        params, batches, coeffs = self._setup(seed=9)
        comp_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                             gamma=1.0))
        dense_fn = jax.jit(make_fl_round_step(model, lr_local=1e-2,
                                              compress=False))
        p1, _ = comp_fn(params, batches, coeffs, jnp.ones((self.C,)))
        p2, _ = dense_fn(params, batches, coeffs, jnp.ones((self.C,)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
