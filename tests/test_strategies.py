"""Strategy plugin registry (core.strategies): capability validation,
bit-exact pre-registry goldens for the five built-ins across the host
engines, third-party registration running through every engine untouched,
and the qtopk registry-only plugin (int8 codec + EF + packed wire).

The goldens were captured on the pre-registry tree (the closed strategy
enum), re-pinned for the installed jax (see ``GOLDENS``), and are asserted
EXACTLY: the registry refactor — and any strategy
added after it — must not move a single bit of the built-ins' trajectories,
comm times, or EF residuals.
"""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import strategies
from repro.core.aggregation import AggregationConfig
from repro.fed import engine as engine_mod
from repro.fed.simulation import FLSimConfig, run_fl

REPO = pathlib.Path(__file__).resolve().parent.parent

# seeded config the goldens were captured with (pre-registry tree)
GOLDEN_SIM = dict(n_clients=8, participation=0.5, rounds=8, n_train=1600,
                  n_test=400, dim=48, hidden=48, n_classes=8, batch_size=32,
                  eval_every=3, seed=3)
GOLDEN_CR = 0.1

#: pinned under jax 0.9.0 (CPU): jax 0.5 made ``jax_threefry_partitionable``
#: the default, which changed the random stream the seeded simulation draws
#: from, so the values captured on the pre-registry tree (jax 0.4.37) moved;
#: the engines stayed bit-identical to one another
GOLDENS = json.loads(r"""
{
 "fedavg": {
  "legacy": {
   "accuracies": [
    [
     0,
     0.4424999952316284
    ],
    [
     3,
     0.7174999713897705
    ],
    [
     6,
     0.5674999952316284
    ],
    [
     7,
     0.8999999761581421
    ]
   ],
   "comm_actual": 2.7352610533509347,
   "residual_sum": null
  },
  "fused": {
   "accuracies": [
    [
     0,
     0.4424999952316284
    ],
    [
     3,
     0.7174999713897705
    ],
    [
     6,
     0.5674999952316284
    ],
    [
     7,
     0.8999999761581421
    ]
   ],
   "comm_actual": 2.7352610533509347,
   "residual_sum": null
  },
  "scan": {
   "accuracies": [
    [
     0,
     0.4424999952316284
    ],
    [
     3,
     0.7174999713897705
    ],
    [
     6,
     0.5674999952316284
    ],
    [
     7,
     0.8999999761581421
    ]
   ],
   "comm_actual": 2.7352610533509347,
   "residual_sum": null
  }
 },
 "topk": {
  "legacy": {
   "accuracies": [
    [
     0,
     0.32749998569488525
    ],
    [
     3,
     0.5899999737739563
    ],
    [
     6,
     0.5199999809265137
    ],
    [
     7,
     0.7924999594688416
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  },
  "fused": {
   "accuracies": [
    [
     0,
     0.32749998569488525
    ],
    [
     3,
     0.5899999737739563
    ],
    [
     6,
     0.5199999809265137
    ],
    [
     7,
     0.7924999594688416
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  },
  "scan": {
   "accuracies": [
    [
     0,
     0.32749998569488525
    ],
    [
     3,
     0.5899999737739563
    ],
    [
     6,
     0.5199999809265137
    ],
    [
     7,
     0.7924999594688416
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  }
 },
 "eftopk": {
  "legacy": {
   "accuracies": [
    [
     0,
     0.32749998569488525
    ],
    [
     3,
     0.6875
    ],
    [
     6,
     0.5699999928474426
    ],
    [
     7,
     0.8424999713897705
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": 72.94383239746094
  },
  "fused": {
   "accuracies": [
    [
     0,
     0.32749998569488525
    ],
    [
     3,
     0.6875
    ],
    [
     6,
     0.5699999928474426
    ],
    [
     7,
     0.8424999713897705
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": 72.94383239746094
  },
  "scan": {
   "accuracies": [
    [
     0,
     0.32749998569488525
    ],
    [
     3,
     0.6875
    ],
    [
     6,
     0.5699999928474426
    ],
    [
     7,
     0.8424999713897705
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": 72.94383239746094
  }
 },
 "bcrs": {
  "legacy": {
   "accuracies": [
    [
     0,
     0.42499998211860657
    ],
    [
     3,
     0.6875
    ],
    [
     6,
     0.7400000095367432
    ],
    [
     7,
     0.875
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  },
  "fused": {
   "accuracies": [
    [
     0,
     0.42499998211860657
    ],
    [
     3,
     0.6875
    ],
    [
     6,
     0.7400000095367432
    ],
    [
     7,
     0.875
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  },
  "scan": {
   "accuracies": [
    [
     0,
     0.42499998211860657
    ],
    [
     3,
     0.6875
    ],
    [
     6,
     0.7400000095367432
    ],
    [
     7,
     0.875
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  }
 },
 "bcrs_opwa": {
  "legacy": {
   "accuracies": [
    [
     0,
     0.5649999976158142
    ],
    [
     3,
     0.35249999165534973
    ],
    [
     6,
     0.8224999904632568
    ],
    [
     7,
     0.7549999952316284
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  },
  "fused": {
   "accuracies": [
    [
     0,
     0.5649999976158142
    ],
    [
     3,
     0.35249999165534973
    ],
    [
     6,
     0.8224999904632568
    ],
    [
     7,
     0.7549999952316284
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  },
  "scan": {
   "accuracies": [
    [
     0,
     0.5649999976158142
    ],
    [
     3,
     0.35249999165534973
    ],
    [
     6,
     0.8224999904632568
    ],
    [
     7,
     0.7549999952316284
    ]
   ],
   "comm_actual": 1.7293823236740713,
   "residual_sum": null
  }
 }
}
"""
)


def _snapshot(res):
    return {
        "accuracies": [[int(a), float(b)] for a, b in res.accuracies],
        "comm_actual": float(res.times.actual),
        "residual_sum": (float(np.abs(res.final_residuals).sum())
                         if res.final_residuals is not None else None),
    }


def _run(strategy, engine, **overrides):
    sim_kw = dict(GOLDEN_SIM)
    sim_kw.update(overrides)
    acfg = AggregationConfig(strategy=strategy, cr=GOLDEN_CR)
    return run_fl(FLSimConfig(**sim_kw), acfg, engine=engine)


#: cheap config for parity tests that do not need the golden trajectory
FAST_SIM = dict(n_clients=6, participation=0.5, rounds=4, n_train=480,
                n_test=120, dim=16, hidden=16, n_classes=4, batch_size=32,
                eval_every=2, seed=5)


# ---------------------------------------------------------------- wire format
class TestWireFormat:
    def test_bytes_on_wire(self):
        assert strategies.DENSE32.bytes_on_wire(1000, 10) == 4000.0
        assert strategies.SPARSE32.bytes_on_wire(1000, 10) == 80.0
        assert strategies.PACKED_INT8.bytes_on_wire(1000, 10) == 54.0

    def test_cr_eff_reference_pair_is_identity(self):
        # bitwise: the pre-registry accounting multiplied by nothing, so
        # the reference pair must return the input object unchanged
        cr = 0.1
        assert strategies.SPARSE32.cr_eff(cr) is cr
        crs = np.asarray([0.1, 0.03])
        assert strategies.SPARSE32.cr_eff(crs) is crs

    def test_cr_eff_dense_is_one(self):
        assert strategies.DENSE32.cr_eff(0.1) == 1.0
        np.testing.assert_array_equal(
            strategies.DENSE32.cr_eff(np.asarray([0.1, 0.5])),
            np.asarray([1.0, 1.0]))

    def test_cr_eff_packed(self):
        n = 1000
        got = strategies.PACKED_INT8.cr_eff(0.1, n)
        assert got == 0.1 * (5.0 / 8.0) + 4.0 / (8.0 * n)
        with pytest.raises(ValueError, match="needs n_params"):
            strategies.PACKED_INT8.cr_eff(0.1)

    def test_cr_eff_prices_exact_wire_bytes(self):
        # cr_eff is DEFINED as: the cr that makes the paper's 2x-reference
        # comm_time charge this format's exact payload bytes
        n, cr = 4096, 0.07
        k = int(round(cr * n))
        eff = strategies.PACKED_INT8.cr_eff(k / n, n)
        assert np.isclose(eff * 8.0 * n,
                          strategies.PACKED_INT8.bytes_on_wire(n, k))


# -------------------------------------------------------------- registration
class TestRegistration:
    def test_duplicate_name_refused(self):
        with pytest.raises(ValueError, match="already registered"):
            strategies.register(strategies.Strategy(name="topk"))

    def test_unknown_capability_values_refused(self):
        with pytest.raises(ValueError, match="unknown carry"):
            strategies.register(strategies.Strategy(name="x", carry="elf"))
        with pytest.raises(ValueError, match="unknown selector"):
            strategies.register(
                strategies.Strategy(name="x", selector="bottomk"))
        with pytest.raises(ValueError, match="unknown weighting"):
            strategies.register(
                strategies.Strategy(name="x", weighting="uniform"))

    def test_codec_requires_ef_carry(self):
        with pytest.raises(ValueError, match="requires carry='ef'"):
            strategies.register(strategies.Strategy(
                name="x", carry="none",
                value_codec=strategies.int8_symmetric_codec,
                megakernel=False))

    def test_codec_megakernel_needs_kernel_codec(self):
        """value_codec + megakernel=True is only legal when the codec has a
        registered kernel lowering (fused_merge's dequantization stage)."""
        with pytest.raises(ValueError, match="kernel_codec"):
            strategies.register(strategies.Strategy(
                name="x", carry="ef",
                value_codec=strategies.int8_symmetric_codec,
                megakernel=True))

    def test_kernel_codec_requires_value_codec(self):
        with pytest.raises(ValueError, match="value_codec"):
            strategies.register(strategies.Strategy(
                name="x", carry="ef", kernel_codec="int8"))

    def test_unknown_kernel_codec_refused(self):
        with pytest.raises(ValueError, match="unknown kernel_codec"):
            strategies.register(strategies.Strategy(
                name="x", carry="ef",
                value_codec=strategies.int8_symmetric_codec,
                kernel_codec="fp8", megakernel=True))

    def test_dense_selector_needs_dense_wire(self):
        with pytest.raises(ValueError, match="dense wire"):
            strategies.register(strategies.Strategy(
                name="x", selector="none", wire=strategies.SPARSE32,
                megakernel=False))
        with pytest.raises(ValueError, match="misprice"):
            strategies.register(strategies.Strategy(
                name="x", selector="topk", wire=strategies.DENSE32))

    def test_unknown_name_lists_registered(self):
        with pytest.raises(ValueError, match="registered: fedavg"):
            strategies.get("nope")

    def test_config_time_errors(self):
        with pytest.raises(ValueError, match="unknown strategy 'nope'"):
            AggregationConfig(strategy="nope")
        from repro.launch.fl_train import FLTrainConfig
        with pytest.raises(ValueError, match="unknown strategy 'nope'"):
            FLTrainConfig(strategy="nope")

    def test_no_strategy_enum_matching_outside_registry(self):
        """The CI guard, run in-suite: engines dispatch on capabilities."""
        sys.path.insert(0, str(REPO / "tools"))
        try:
            from check_strategy_enum import check
        finally:
            sys.path.pop(0)
        assert check(REPO) == []


# ------------------------------------------------------------------- goldens
class TestBuiltinGoldens:
    """The five built-ins, three host engines, captured pre-registry: the
    registry refactor must be invisible at the bit level."""

    @pytest.mark.parametrize("strategy", list(GOLDENS))
    def test_bit_exact_with_pre_registry_tree(self, strategy):
        for engine in ("legacy", "fused", "scan"):
            got = _snapshot(_run(strategy, engine))
            assert got == GOLDENS[strategy][engine], (strategy, engine)


# -------------------------------------------------- third-party registration
@pytest.fixture
def toy_eftopk():
    """A 'third-party' strategy: an exact capability clone of eftopk under a
    new name, registered through the public API only."""
    name = "toy_eftopk"
    strategies.register(strategies.Strategy(
        name=name, description="third-party EF Top-K clone",
        carry="ef", selector="topk", weighting="data",
        wire=strategies.SPARSE32, megakernel=True))
    try:
        yield name
    finally:
        strategies.unregister(name)


class TestThirdPartyStrategy:
    """A strategy registered in a test file runs through every engine with
    no engine edits — and, being a capability clone of eftopk, must
    reproduce eftopk's trajectory bitwise."""

    def test_host_engines_parity_and_one_trace(self, toy_eftopk):
        ref = {e: _snapshot(_run("eftopk", e, **FAST_SIM))
               for e in ("legacy", "fused", "scan")}
        key = ("sim_scan", toy_eftopk, False)
        traces0 = engine_mod.TRACE_COUNTS[key]
        for engine in ("legacy", "fused", "scan"):
            got = _snapshot(_run(toy_eftopk, engine, **FAST_SIM))
            assert got == ref[engine], engine
        assert engine_mod.TRACE_COUNTS[key] - traces0 == 1

    def test_mesh_engine_parity_and_one_trace(self, toy_eftopk):
        from repro.fed import mesh_round
        from repro.fed.engine import init_mesh_residuals, make_mesh_sim_scan

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            err = pred - batch["t"]
            return jnp.mean(err * err), pred

        rng = np.random.default_rng(0)
        t, c, s, b, dim, out = 3, 3, 2, 4, 8, 3
        params = {"w": jnp.asarray(rng.normal(size=(dim, out)), jnp.float32)}
        xs = {"batches": {
                  "x": jnp.asarray(rng.normal(size=(t, c, s, b, dim)),
                                   jnp.float32),
                  "t": jnp.asarray(rng.normal(size=(t, c, s, b, out)),
                                   jnp.float32)},
              "step_mask": jnp.ones((t, c, s), bool),
              "active": jnp.ones((t, c), bool),
              "weights": jnp.full((t, c), 1.0 / c, jnp.float32),
              "crs": jnp.full((t, c), 0.25, jnp.float32)}
        outs = {}
        for name in ("eftopk", toy_eftopk):
            key = ("mesh_scan", name)
            traces0 = engine_mod.TRACE_COUNTS[key]
            sim = make_mesh_sim_scan(loss_fn, params, lr=1e-2, strategy=name)
            outs[name] = sim(jax.tree.map(jnp.copy, params),
                             init_mesh_residuals(params, c), xs)
            assert engine_mod.TRACE_COUNTS[key] - traces0 == 1
        for field in ("params", "residuals"):
            for a, b in zip(jax.tree.leaves(outs["eftopk"][field]),
                            jax.tree.leaves(outs[toy_eftopk][field])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(outs["eftopk"]["ys"]["loss"]),
            np.asarray(outs[toy_eftopk]["ys"]["loss"]))


# --------------------------------------------------------------------- qtopk
class TestQtopk:
    """The shipped registry-only plugin: int8-quantized Top-K survivors.
    No engine file mentions it (asserted), yet it runs end-to-end through
    all engines with EF absorbing the quantization error and the packed
    wire format pricing its uploads 8/5x cheaper than idx32+f32."""

    def test_no_engine_code_mentions_qtopk(self):
        """Docstrings may cite qtopk as the registry-only example; no engine
        may reference it STRUCTURALLY (identifiers or non-docstring string
        literals) — that would mean the plugin needed an engine edit."""
        import ast
        engines = ["src/repro/fed/server.py", "src/repro/fed/round_step.py",
                   "src/repro/fed/engine.py", "src/repro/fed/mesh_round.py",
                   "src/repro/fed/simulation.py", "src/repro/dist/grad_sync.py",
                   "src/repro/core/aggregation.py",
                   "src/repro/launch/fl_train.py"]
        for rel in engines:
            tree = ast.parse((REPO / rel).read_text())
            doc_ids = set()
            for node in ast.walk(tree):
                if isinstance(node, (ast.Module, ast.ClassDef,
                                     ast.FunctionDef, ast.AsyncFunctionDef)):
                    body = node.body
                    if (body and isinstance(body[0], ast.Expr)
                            and isinstance(body[0].value, ast.Constant)):
                        doc_ids.add(id(body[0].value))
            for node in ast.walk(tree):
                if (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)
                        and id(node) not in doc_ids):
                    assert "qtopk" not in node.value, (rel, node.value)
                if isinstance(node, ast.Name):
                    assert "qtopk" not in node.id, rel

    def test_engines_agree_and_ef_absorbs_quantization(self):
        snaps, finals = {}, {}
        for engine in ("legacy", "fused", "scan"):
            res = _run("qtopk", engine, **FAST_SIM)
            snaps[engine] = _snapshot(res)
            finals[engine] = res
        assert snaps["legacy"] == snaps["fused"] == snaps["scan"]
        # EF must be live: quantization error lands in the residuals
        assert snaps["legacy"]["residual_sum"] > 0.0
        # and the codec must actually change the trajectory vs plain eftopk
        ef = _snapshot(_run("eftopk", "fused", **FAST_SIM))
        assert snaps["fused"]["accuracies"] != ef["accuracies"] or \
            snaps["fused"]["residual_sum"] != ef["residual_sum"]

    def test_packed_wire_cheaper_than_reference_pair(self):
        q = _snapshot(_run("qtopk", "fused", **FAST_SIM))
        ef = _snapshot(_run("eftopk", "fused", **FAST_SIM))
        # identical selection CRs, packed values: strictly cheaper uploads,
        # and (latency aside) by about the 5/8 byte ratio
        assert q["comm_actual"] < ef["comm_actual"]

    def test_codec_roundtrip_properties(self):
        rng = np.random.default_rng(7)
        v = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32))
        mask = jnp.abs(v) > 0.5
        v = jnp.where(mask, v, 0.0)
        deq = strategies.int8_symmetric_codec(v, mask)
        # zeros stay exactly zero (non-survivors never leak value)
        np.testing.assert_array_equal(np.asarray(deq)[~np.asarray(mask)], 0.0)
        # per-client max |v| is on the grid's end point -> reconstructed
        # exactly; everything else within half a step
        scale = np.abs(np.asarray(v)).max(axis=1, keepdims=True) / 127.0
        assert np.all(np.abs(np.asarray(deq - v)) <= scale / 2 + 1e-7)

    def test_mesh_engine_runs_qtopk(self):
        from repro.fed.engine import init_mesh_residuals, make_mesh_sim_scan

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            err = pred - batch["t"]
            return jnp.mean(err * err), pred

        rng = np.random.default_rng(1)
        t, c, s, b, dim, out = 2, 3, 2, 4, 8, 3
        params = {"w": jnp.asarray(rng.normal(size=(dim, out)), jnp.float32)}
        xs = {"batches": {
                  "x": jnp.asarray(rng.normal(size=(t, c, s, b, dim)),
                                   jnp.float32),
                  "t": jnp.asarray(rng.normal(size=(t, c, s, b, out)),
                                   jnp.float32)},
              "step_mask": jnp.ones((t, c, s), bool),
              "active": jnp.ones((t, c), bool),
              "weights": jnp.full((t, c), 1.0 / c, jnp.float32),
              "crs": jnp.full((t, c), 0.25, jnp.float32)}
        sim = make_mesh_sim_scan(loss_fn, params, lr=1e-2, strategy="qtopk")
        out = sim(jax.tree.map(jnp.copy, params),
                  init_mesh_residuals(params, c), xs)
        assert np.isfinite(np.asarray(out["ys"]["loss"])).all()
        # quantization error landed in the per-leaf residuals
        assert sum(float(np.abs(np.asarray(l)).sum())
                   for l in jax.tree.leaves(out["residuals"])) > 0.0

    def test_pod_sync_accepts_registry_strategy(self):
        """dist.grad_sync consumes the registry: qtopk picks the codec, a
        non-compressing strategy is refused."""
        from repro.dist.grad_sync import make_compressed_train_step

        class TinyModel:
            @staticmethod
            def loss_fn(params, batch):
                pred = batch["x"] @ params["w"]
                loss = jnp.mean((pred - batch["t"]) ** 2)
                return loss, {"mse": loss}

        class SGD:
            @staticmethod
            def init(params):
                return ()

            @staticmethod
            def update(grads, state, params):
                return (jax.tree.map(lambda p, g: p - 1e-2 * g,
                                     params, grads), state)

        with pytest.raises(ValueError, match="does not compress"):
            make_compressed_train_step(TinyModel, SGD, n_pods=2,
                                       strategy="fedavg")
        step = jax.jit(make_compressed_train_step(
            TinyModel, SGD, n_pods=2, wire_cr=0.5, min_leaf_size=1,
            strategy="qtopk"))
        rng = np.random.default_rng(2)
        params = {"w": jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)}
        batch = {"x": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
                 "t": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
        from repro.dist.grad_sync import init_compressed_state
        state = init_compressed_state(SGD, params, n_pods=2)
        new_params, new_state, out = step(params, state,
                                          batch, jnp.full((2,), 0.5),
                                          jnp.full((2,), 0.5))
        assert np.isfinite(float(out["loss"]))
        assert float(jnp.abs(jax.tree.leaves(new_state["ef"])[0]).sum()) > 0.0


class TestCodecNumerics:
    """satellite coverage for the shared quantization op sequence: the
    zero-row path, the elementwise round-trip bound, and the exact-product
    scale rounding that makes the kernel route fma-immune."""

    def test_scale_mantissa_bits(self):
        # 23 - ceil(log2(levels + 1)): q in [-levels, levels] has
        # <= ceil(log2(levels+1)) + 1 significand bits, so q * scale fits
        # f32's 24 exactly
        assert strategies.scale_mantissa_bits(127.0) == 16
        assert strategies.scale_mantissa_bits(7.0) == 20

    def test_zero_rows_dequantize_to_exact_zeros(self):
        # the old 1e-30 scale floor is gone: an all-zero row has scale 0 and
        # the safe-divisor where() keeps every output exactly 0.0
        v = jnp.zeros((3, 64), jnp.float32)
        mask = jnp.zeros((3, 64), bool)
        for codec in (strategies.int8_symmetric_codec,
                      strategies.int4_symmetric_codec):
            out = np.asarray(codec(v, mask))
            assert not np.any(out)
            assert not np.signbit(out).any()

    def test_mixed_zero_and_live_rows(self):
        rng = np.random.default_rng(11)
        v = rng.normal(size=(4, 128)).astype(np.float32)
        v[2] = 0.0
        deq = np.asarray(strategies.int4_symmetric_codec(
            jnp.asarray(v), jnp.asarray(v) != 0))
        assert not np.any(deq[2])
        assert np.any(deq[[0, 1, 3]])

    @pytest.mark.parametrize("codec_name", ["int8", "int4"])
    def test_roundtrip_error_at_most_half_step_elementwise(self, codec_name):
        # |dequant(v) - v| <= scale/2 elementwise, with the documented
        # <= 2^-16-relative scale slack from quantization_scale's
        # reciprocal-multiply + mantissa rounding (clip at the grid edge
        # turns that scale perturbation into levels * |dscale| of error)
        levels = strategies.CODEC_LEVELS[codec_name]
        fn = (strategies.int8_symmetric_codec if codec_name == "int8"
              else strategies.int4_symmetric_codec)
        for seed in range(25):
            rng = np.random.default_rng(seed)
            c, n = int(rng.integers(1, 6)), int(rng.integers(1, 400))
            v = rng.normal(size=(c, n)).astype(np.float32)
            v *= 10.0 ** rng.integers(-10, 10, size=(c, 1)).astype(np.float32)
            v[rng.random(size=(c, n)) < 0.5] = 0.0
            if rng.random() < 0.3:
                v[rng.integers(c)] = 0.0
            vj = jnp.asarray(v)
            deq = np.asarray(fn(vj, vj != 0))
            absmax = np.abs(v).max(axis=1, keepdims=True)
            scale = np.asarray(strategies.quantization_scale(
                jnp.asarray(absmax), levels))
            bound = scale / 2.0 + absmax * 2.0 ** -15
            assert np.all(np.abs(deq - v) <= bound), seed
            # non-survivors (exact zeros) never leak value
            np.testing.assert_array_equal(deq[v == 0.0], 0.0)

    def test_quantization_scale_products_are_exact_in_f32(self):
        # the whole point of the mantissa rounding: every q * scale is
        # exactly representable, so fma contraction and mul-then-sub agree
        # under any lowering — verified against float64 ground truth
        rng = np.random.default_rng(12)
        absmax = jnp.asarray(
            (rng.random(4096).astype(np.float32) + 1e-6)
            * 10.0 ** rng.integers(-30, 30, size=4096).astype(np.float32))
        for levels in strategies.CODEC_LEVELS.values():
            scale = np.asarray(strategies.quantization_scale(absmax, levels))
            qs = np.arange(-levels, levels + 1, dtype=np.float32)
            prod32 = qs[None, :] * scale[:, None]
            prod64 = qs[None, :].astype(np.float64) * scale[:, None]
            np.testing.assert_array_equal(prod32.astype(np.float64), prod64)


class TestInt4Strategy:
    """Registration sanity + wire accounting for the int4 plugin and the
    bitmask wire formats that ride along."""

    def test_registered_capabilities(self):
        s = strategies.get("int4")
        assert s.carry == "ef" and s.selector == "topk"
        assert s.value_codec is strategies.int4_symmetric_codec
        assert s.megakernel and s.kernel_codec == "int4"
        assert s.wire is strategies.PACKED_INT4
        q = strategies.get("qtopk")
        assert q.megakernel and q.kernel_codec == "int8"

    def test_packed_int4_bytes_on_wire(self):
        # idx32 + int4 + scale32: 4k + 0.5k + 4
        assert strategies.PACKED_INT4.bytes_on_wire(1000, 10) == 49.0
        # vs the idx32 + f32 reference pair's 8k = 80: the 9/16 ratio
        assert strategies.PACKED_INT4.bytes_on_wire(10 ** 6, 10 ** 5) \
            / strategies.SPARSE32.bytes_on_wire(10 ** 6, 10 ** 5) \
            == pytest.approx(9.0 / 16.0, rel=1e-4)

    def test_bitmask_bytes_on_wire(self):
        # bitmask + int8 + scale32: n/8 + 1k + 4
        assert strategies.BITMASK_INT8.bytes_on_wire(1000, 10) == 139.0
        # bitmask + int4 + scale32: n/8 + 0.5k + 4
        assert strategies.BITMASK_INT4.bytes_on_wire(1000, 10) == 134.0
        # dense-ish selection: the 1-bit mask beats 4-byte indices when
        # k/n > 1/32
        n = 10 ** 5
        for k in (n // 10, n // 5):
            assert strategies.BITMASK_INT8.bytes_on_wire(n, k) \
                < strategies.PACKED_INT8.bytes_on_wire(n, k)
        assert strategies.BITMASK_INT8.bytes_on_wire(n, n // 100) \
            > strategies.PACKED_INT8.bytes_on_wire(n, n // 100)

    def test_cr_eff_prices_exact_wire_bytes(self):
        # comm_time's 2x factor charges 8 * n * cr bytes for the reference
        # pair, so cr_eff is DEFINED by 8 * n * cr_eff == bytes_on_wire
        n = 10 ** 6
        for wf in (strategies.PACKED_INT4, strategies.BITMASK_INT8,
                   strategies.BITMASK_INT4):
            for k in (10, 10 ** 4, 10 ** 5):
                eff = wf.cr_eff(k / n, n)
                np.testing.assert_allclose(8.0 * n * float(eff),
                                           wf.bytes_on_wire(n, k), rtol=1e-9)


class TestBitmaskTopkStrategy:
    """The bitmask-wire built-in: qtopk's exact math (topk + int8 codec +
    EF + data weighting) shipped under a 1-bit coordinate bitmask instead
    of packed idx32 — the strategy that exercises the BITMASK_* mask-bits
    pricing end to end."""

    def test_registered_capabilities(self):
        s = strategies.get("bitmask_topk")
        assert s.carry == "ef" and s.selector == "topk"
        assert s.value_codec is strategies.int8_symmetric_codec
        assert s.weighting == "data"
        assert s.wire is strategies.BITMASK_INT8
        assert s.megakernel and s.kernel_codec == "int8"
        assert s.residual_layout == "dense"

    def test_wire_pricing_beats_packed_indices_above_break_even(self):
        # mask bits amortize over n: above k/n = 1/32 the bitmask wire is
        # strictly cheaper than packed idx32 + int8; below it, dearer
        s = strategies.get("bitmask_topk")
        n = 10 ** 4
        eff = float(s.wire.cr_eff(0.05, n))
        # n/8 + k + 4 bytes over the 8k-byte reference pair
        k = int(0.05 * n)
        np.testing.assert_allclose(
            eff, (n / 8.0 + k + 4.0) / (8.0 * n), rtol=1e-12)
        assert eff < float(strategies.PACKED_INT8.cr_eff(0.05, n))
        assert float(s.wire.cr_eff(0.01, n)) \
            > float(strategies.PACKED_INT8.cr_eff(0.01, n))

    def test_same_trajectory_as_qtopk_cheaper_comm(self):
        """Wire format is accounting only: the bitmask_topk trajectory is
        bit-identical to qtopk's (same selector, codec, EF carry), while
        its comm time is strictly lower at GOLDEN_CR = 10% density — the
        regime where the 1-bit mask beats 4-byte indices."""
        bm = _run("bitmask_topk", "fused", **FAST_SIM)
        q = _run("qtopk", "fused", **FAST_SIM)
        assert _snapshot(bm)["accuracies"] == _snapshot(q)["accuracies"]
        np.testing.assert_array_equal(bm.final_residuals, q.final_residuals)
        assert bm.times.actual < q.times.actual
