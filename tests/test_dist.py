"""repro.dist tests: sharding-rule resolution per arch family, constrain
no-op semantics, train-step smoke, and the compressed-step parity guarantee
(wire_cr=1.0 reproduces the dense step — strict generalization)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.dist import sharding as shd
from repro.dist.grad_sync import (init_compressed_state,
                                  make_compressed_train_step, make_train_step)
from repro.launch.mesh import make_mesh_from_spec
from repro.models import Model
from repro.optim import make_optimizer

B, S = 4, 32


def _mesh(axes=("data", "model")):
    return make_mesh_from_spec((1,) * len(axes), axes)


def _batch(cfg, b=B, s=S):
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (b, s + 1))
    out = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
           "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    if cfg.family == "encdec":
        out["frames"] = jnp.full((b, s, cfg.d_model), 0.1, jnp.float32)
    if cfg.family == "vlm":
        v = cfg.vision
        out["patches"] = jnp.full((b, v.n_patches, v.d_vision), 0.1,
                                  jnp.float32)
    return out


def _setup(arch="stablelm-1.6b", lr=0.1):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("sgd", lr)
    return cfg, model, params, opt


# ---------------------------------------------------------------- rules
class TestRules:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_resolution_per_family(self, arch):
        cfg = get_config(arch)
        rules = shd.make_rules(cfg, SHAPES["train_4k"], _mesh())
        assert rules.batch_axes == ("data",)
        assert rules.shard_batch
        assert rules.logical(("batch", "seq", "embed")) == \
            P(("data",), None, None)
        assert rules.logical(("batch", "vocab")) == P(("data",), "model")
        # act_d shards over model only for FSDP archs
        fsdp = cfg.n_params() >= cfg.fsdp_threshold
        assert rules.fsdp == fsdp
        assert rules.logical(("act_d",)) == (P("model") if fsdp else P(None))

    def test_multi_pod_batch_axes(self):
        cfg = get_config("stablelm-1.6b")
        mesh = _mesh(("pod", "data", "model"))
        rules = shd.make_rules(cfg, SHAPES["train_4k"], mesh)
        assert rules.batch_axes == ("pod", "data")
        assert rules.logical(("batch",)) == P(("pod", "data"))

    def test_unknown_logical_axis_replicates(self):
        rules = shd.make_rules(get_config("yi-9b"), SHAPES["train_4k"],
                               _mesh())
        assert rules.logical(("batch", "no_such_axis")) == P(("data",), None)

    def test_param_specs_structure(self):
        cfg, model, _, _ = _setup("yi-9b")
        params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        with shd.use_rules(shd.make_rules(cfg, SHAPES["train_4k"], _mesh())):
            pspecs = shd.param_specs(cfg, params_abs)
        assert jax.tree.structure(pspecs, is_leaf=lambda x: isinstance(x, P)) \
            == jax.tree.structure(params_abs)
        assert all(isinstance(sp, P) for sp in jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P)))


class TestConstrain:
    def test_noop_without_rules(self):
        assert shd.get_rules() is None
        x = jnp.ones((2, 3))
        assert shd.constrain(x, ("batch", "embed")) is x

    def test_identity_value_under_rules(self):
        cfg = get_config("stablelm-1.6b")
        x = jnp.arange(12.0).reshape(4, 3)
        with shd.use_rules(shd.make_rules(cfg, SHAPES["train_4k"], _mesh())):
            y = jax.jit(lambda a: shd.constrain(a, ("batch", "embed")))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_rank_mismatch_passes_through(self):
        cfg = get_config("stablelm-1.6b")
        x = jnp.ones((2, 3, 4))
        with shd.use_rules(shd.make_rules(cfg, SHAPES["train_4k"], _mesh())):
            assert shd.constrain(x, ("batch",)) is x

    def test_use_rules_restores(self):
        cfg = get_config("stablelm-1.6b")
        with shd.use_rules(shd.make_rules(cfg, SHAPES["train_4k"], _mesh())):
            assert shd.get_rules() is not None
        assert shd.get_rules() is None


# ------------------------------------------------------------- dense step
class TestTrainStep:
    def test_loss_decreases(self):
        cfg, model, params, opt = _setup()
        step = jax.jit(make_train_step(model, opt))
        state, batch = opt.init(params), _batch(cfg)
        losses = []
        for _ in range(5):
            params, state, m = step(params, state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    def test_n_micro_matches_full_batch(self):
        cfg, model, params, opt = _setup(lr=0.05)
        batch = _batch(cfg)
        p1, _, m1 = jax.jit(make_train_step(model, opt))(
            params, opt.init(params), batch)
        p2, _, m2 = jax.jit(make_train_step(model, opt, n_micro=2))(
            params, opt.init(params), batch)
        assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- compressed step
class TestCompressedStep:
    def test_wire_cr_one_matches_dense(self):
        """cr=1.0 keeps every coordinate: the compressed step must reproduce
        the dense update (strict generalization, not a fork)."""
        n_pods = 2
        cfg, model, params, opt = _setup(lr=0.05)
        batch = _batch(cfg)
        dense = jax.jit(make_train_step(model, opt))
        comp = jax.jit(make_compressed_train_step(
            model, opt, n_pods=n_pods, wire_cr=1.0, gamma=3.0,
            use_kernel=False))
        crs = jnp.ones((n_pods,), jnp.float32)
        coeffs = jnp.full((n_pods,), 1.0 / n_pods, jnp.float32)
        p1, _, m1 = dense(params, opt.init(params), batch)
        p2, s2, m2 = comp(params, init_compressed_state(opt, params,
                                                        n_pods=n_pods),
                          batch, crs, coeffs)
        assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-4, atol=1e-6)
        # nothing was dropped -> error-feedback residuals stay zero
        assert max(float(jnp.max(jnp.abs(e)))
                   for e in jax.tree.leaves(s2["ef"])) == 0.0

    def test_ef_residual_carried_and_loss_finite(self):
        n_pods = 2
        cfg, model, params, opt = _setup(lr=0.05)
        step = jax.jit(make_compressed_train_step(
            model, opt, n_pods=n_pods, wire_cr=0.05, gamma=2.0,
            use_kernel=False))
        state = init_compressed_state(opt, params, n_pods=n_pods)
        crs = jnp.full((n_pods,), 0.05, jnp.float32)
        coeffs = jnp.full((n_pods,), 1.0 / n_pods, jnp.float32)
        for i in range(3):
            params, state, m = step(params, state, _batch(cfg), crs, coeffs)
            assert np.isfinite(float(m["loss"]))
        # at cr<1 the top-k drop leaves nonzero residual on the big leaves
        assert max(float(jnp.max(jnp.abs(e)))
                   for e in jax.tree.leaves(state["ef"])) > 0.0

    def test_bare_opt_state_structure_preserved(self):
        """launch/specs.py lowers with a bare opt.init state: in/out
        structures must match for out_shardings + donation."""
        n_pods = 2
        cfg, model, params, opt = _setup()
        step = make_compressed_train_step(model, opt, n_pods=n_pods,
                                          wire_cr=0.1, use_kernel=False)
        state = opt.init(params)
        crs = jnp.full((n_pods,), 0.1, jnp.float32)
        coeffs = jnp.full((n_pods,), 0.5, jnp.float32)
        _, new_state, m = jax.jit(step)(params, state, _batch(cfg), crs,
                                        coeffs)
        assert jax.tree.structure(new_state) == jax.tree.structure(state)
        assert np.isfinite(float(m["loss"]))

    def test_batch_not_divisible_raises(self):
        cfg, model, params, opt = _setup()
        step = make_compressed_train_step(model, opt, n_pods=3, wire_cr=0.1)
        with pytest.raises(ValueError, match="not divisible"):
            step(params, opt.init(params), _batch(cfg),
                 jnp.ones((3,)), jnp.ones((3,)) / 3)
