"""Scanned mesh driver: the pytree-native multi-round `lax.scan` program
(`engine.make_mesh_sim_scan` / `mesh_round.make_round_body`) must be
bit-exact with the per-round dispatch loop, carry EF residuals with the
flat [C, n] `engine.compress_merge_leaf` semantics, compile once per
checkpoint chunk,
and checkpoint/restart without perturbing the trajectory."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bcrs as bcrs_mod
from repro.core import strategies
from repro.core.compression import k_for_ratio, k_for_ratio_traced
from repro.fed import engine as engine_mod
from repro.fed.engine import (compress_merge_leaf, init_mesh_residuals,
                              make_masked_local_trainer, make_mesh_sim_scan)
from repro.fed.mesh_round import make_mesh_round_step

STRATEGIES = ("fedavg", "topk", "bcrs", "bcrs_opwa", "eftopk")


def _loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    err = pred - batch["t"]
    return jnp.mean(err * err), pred


def _setup(seed=0, t=4, c=3, s=2, b=4, dim=12, out=5):
    """Params + T stacked rounds of xs with ragged steps, padded cohort
    slots, and per-client CR spreads."""
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.normal(size=(dim, out)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(out,)), jnp.float32)}
    active = np.zeros((t, c), bool)
    step_mask = np.zeros((t, c, s), bool)
    weights = np.zeros((t, c), np.float32)
    for r in range(t):
        c_r = int(rng.integers(1, c + 1))
        active[r, :c_r] = True
        for j in range(c_r):
            step_mask[r, j, : int(rng.integers(1, s + 1))] = True
        w = rng.dirichlet(np.ones(c_r))
        weights[r, :c_r] = w
    xs = {"batches": {
              "x": jnp.asarray(rng.normal(size=(t, c, s, b, dim)),
                               jnp.float32),
              "t": jnp.asarray(rng.normal(size=(t, c, s, b, out)),
                               jnp.float32)},
          "step_mask": jnp.asarray(step_mask),
          "active": jnp.asarray(active),
          "weights": jnp.asarray(weights),
          "crs": jnp.asarray(rng.uniform(0.05, 0.9, size=(t, c)),
                             jnp.float32)}
    return params, xs


def _residuals0(params, c, strategy):
    return (init_mesh_residuals(params, c) if strategy == "eftopk"
            else jnp.zeros((0,), jnp.float32))


def _copy(tree):
    """The scanned program donates its carry buffers — copy before calling
    when the test reuses the inputs afterwards."""
    return jax.tree.map(jnp.copy, tree)


class TestScanVsRoundLoop:
    """Acceptance: the scanned program equals the per-round jitted step
    dispatched in a Python loop — params trajectory, losses, and EF
    residuals, bitwise."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_bit_exact(self, strategy):
        params, xs = _setup(seed=3)
        t, c = xs["active"].shape
        res0 = _residuals0(params, c, strategy)
        sim = make_mesh_sim_scan(_loss_fn, params, lr=1e-2,
                                 strategy=strategy, gamma=3.0)
        out = sim(_copy(params), _copy(res0), xs)

        from repro.fed import mesh_round
        traces0 = mesh_round.TRACE_COUNTS[(strategy,)]
        step = make_mesh_round_step(_loss_fn, lr_local=1e-2,
                                    strategy=strategy, gamma=3.0,
                                    donate=False)
        p = params
        res = res0 if strategy == "eftopk" else None
        losses = []
        for r in range(t):
            batch_r = jax.tree.map(lambda a: a[r], xs["batches"])
            p, res, loss = step(p, res, batch_r, xs["step_mask"][r],
                                xs["weights"][r], xs["crs"][r],
                                xs["active"][r])
            losses.append(loss)
        # the per-round step is one trace regardless of dispatch count
        assert mesh_round.TRACE_COUNTS[(strategy,)] - traces0 == 1
        for a, b in zip(jax.tree.leaves(out["params"]), jax.tree.leaves(p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(out["ys"]["loss"]),
                                      np.asarray(jnp.stack(losses)))
        if strategy == "eftopk":
            for a, b in zip(jax.tree.leaves(out["residuals"]),
                            jax.tree.leaves(res)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_inactive_rounds_leave_carry_untouched(self):
        """A round whose cohort is entirely padded must be a no-op on the
        params AND the residuals (the plan simply omits dead rounds; this
        guards the padding semantics that makes that sound)."""
        params, xs = _setup(seed=11, t=3)
        dead = jax.tree.map(lambda a: a.at[1].set(jnp.zeros_like(a[1])),
                            {"active": xs["active"],
                             "weights": xs["weights"]})
        xs = {**xs, **dead}
        res0 = _residuals0(params, xs["active"].shape[1], "eftopk")
        sim = make_mesh_sim_scan(_loss_fn, params, lr=1e-2,
                                 strategy="eftopk")
        out = sim(_copy(params), res0, xs)
        # rerun rounds 0 and 2 only -> same endpoint
        xs2 = jax.tree.map(lambda a: a[jnp.asarray([0, 2])], xs)
        out2 = sim(_copy(params), _residuals0(params, xs["active"].shape[1],
                                              "eftopk"), xs2)
        for a, b in zip(jax.tree.leaves(out["params"]),
                        jax.tree.leaves(out2["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(out["residuals"]),
                        jax.tree.leaves(out2["residuals"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestEFCarrySemantics:
    def test_matches_flat_merge(self):
        """On a single flat leaf the per-leaf mesh path and the flat-space
        substrate coincide: the scanned driver's EF residual carry must
        reproduce `compress_merge_leaf` on the flat [C, n] matrix round by
        round, bitwise."""
        rng = np.random.default_rng(7)
        n, c, s, b, t = 64, 3, 2, 4, 4
        params = {"w": jnp.asarray(rng.normal(size=(n,)), jnp.float32)}

        def loss_fn(p, batch):
            pred = batch["x"] @ p["w"]
            err = pred - batch["t"]
            return jnp.mean(err * err), pred

        _, xs = _setup(seed=7, t=t, c=c, s=s, b=b, dim=n, out=1)
        xs["batches"]["t"] = xs["batches"]["t"][..., 0]
        sim = make_mesh_sim_scan(loss_fn, params, lr=1e-2,
                                 strategy="eftopk")
        out = sim(_copy(params), init_mesh_residuals(params, c), xs)

        eftopk = strategies.get("eftopk")
        local = make_masked_local_trainer(loss_fn, 1e-2)
        flat = params["w"]
        res = jnp.zeros((c, n), jnp.float32)
        for r in range(t):
            batch_r = jax.tree.map(lambda a: a[r], xs["batches"])
            deltas, _ = jax.vmap(local, in_axes=(None, 0, 0))(
                {"w": flat}, batch_r, xs["step_mask"][r])
            ks = k_for_ratio_traced(n, xs["crs"][r])
            w = jnp.where(xs["active"][r], xs["weights"][r], 0.0)
            agg, res = compress_merge_leaf(deltas["w"], w, ks, eftopk,
                                           gamma=5.0, overlap_d=1,
                                           use_kernel=False, residuals=res,
                                           active=xs["active"][r])
            flat = flat - agg
        np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                      np.asarray(flat))
        np.testing.assert_array_equal(np.asarray(out["residuals"]["w"]),
                                      np.asarray(res))


class TestChunkCompiles:
    def test_one_trace_per_chunk_shape(self):
        """Equal-length checkpoint chunks reuse ONE executable; only a
        ragged tail chunk costs a second trace."""
        params, xs = _setup(seed=5, t=6)
        key = ("mesh_scan", "bcrs_opwa")
        sim = make_mesh_sim_scan(_loss_fn, params, lr=1e-2,
                                 strategy="bcrs_opwa")
        before = engine_mod.TRACE_COUNTS[key]
        p, res = _copy(params), jnp.zeros((0,), jnp.float32)
        for lo in (0, 2, 4):    # 3 chunks of 2 rounds
            chunk = jax.tree.map(lambda a: a[lo:lo + 2], xs)
            out = sim(p, res, chunk)
            p, res = out["params"], out["residuals"]
        assert engine_mod.TRACE_COUNTS[key] - before == 1
        # a ragged final chunk is a second shape -> exactly one more trace
        out = sim(p, res, jax.tree.map(lambda a: a[:1], xs))
        assert engine_mod.TRACE_COUNTS[key] - before == 2


class TestCompressMergeLeafKernel:
    """Satellite: `use_kernel` is a tri-state plumbed through the per-leaf
    path — "auto" must resolve to the jnp route on CPU bit-exactly, and the
    interpret-mode megakernel route must match the jnp route bitwise."""

    def _inputs(self):
        rng = np.random.default_rng(2)
        u = jnp.asarray(rng.normal(size=(4, 6, 37)), jnp.float32)
        res = jnp.asarray(rng.normal(size=(4, 6, 37)) * 0.3, jnp.float32)
        w = jnp.asarray(rng.random(4), jnp.float32)
        ks = jnp.asarray([1, 20, 222, 100], jnp.int32)
        act = jnp.asarray([True, False, True, True])
        return u, res, w, ks, act

    @pytest.mark.parametrize("opwa", (False, True))
    @pytest.mark.parametrize("ef", (False, True))
    def test_auto_and_kernel_match_jnp(self, opwa, ef):
        u, res, w, ks, act = self._inputs()
        r = res if ef else None
        # grad_sync's convention: residuals switch on EF for any
        # compressing strategy, so OPWA with EF is bcrs_opwa + residuals
        strat = strategies.get("bcrs_opwa" if opwa else "topk")
        outs = {uk: compress_merge_leaf(u, w, ks, strat, gamma=3.0,
                                        overlap_d=1, use_kernel=uk,
                                        residuals=r, active=act)
                for uk in (False, True, "auto")}
        ref_agg, ref_res = outs[False]
        for uk in (True, "auto"):
            agg, new_res = outs[uk]
            np.testing.assert_array_equal(np.asarray(agg),
                                          np.asarray(ref_agg))
            if ef:
                np.testing.assert_array_equal(np.asarray(new_res),
                                              np.asarray(ref_res))

    def test_auto_resolves_to_jnp_off_tpu(self):
        from repro.core.compression import resolve_use_kernel
        if jax.devices()[0].platform != "tpu":
            assert resolve_use_kernel("auto") is False


class TestLeafLayout:
    """One function serves both layouts: a rank-3 leaf merges bit-exact
    with its flat [C, n] view on the jnp route, for every registered
    strategy (selection, codec and merge reduce over all non-client
    axes)."""

    @pytest.mark.parametrize("strategy", strategies.names())
    def test_rank3_matches_flat_view(self, strategy):
        strat = strategies.get(strategy)
        rng = np.random.default_rng(11)
        c, shape = 4, (24, 48)
        n = shape[0] * shape[1]
        u = jnp.asarray(rng.normal(size=(c,) + shape), jnp.float32)
        res = (jnp.asarray(rng.normal(size=(c,) + shape) * 0.3, jnp.float32)
               if strat.needs_residuals else None)
        w = jnp.asarray(rng.random(c) + 0.1, jnp.float32)
        ks = jnp.asarray([1, 57, 400, n], jnp.int32)
        act = jnp.asarray([True, True, False, True])
        kw = dict(gamma=3.0, overlap_d=2, use_kernel=False, active=act)
        agg3, res3 = compress_merge_leaf(u, w, ks, strat, residuals=res,
                                         **kw)
        agg2, res2 = compress_merge_leaf(
            u.reshape(c, n), w, ks, strat,
            residuals=None if res is None else res.reshape(c, n), **kw)
        assert agg3.shape == shape
        np.testing.assert_array_equal(np.asarray(agg3).reshape(n),
                                      np.asarray(agg2))
        if res is not None:
            np.testing.assert_array_equal(np.asarray(res3).reshape(c, n),
                                          np.asarray(res2))


class TestDenseBranch:
    """Strategies that do not compress take the dense merge: padded cohort
    slots are gated out of the f32 cast before one weighted sum, whatever
    the padded rows hold, flat or in a leaf's own layout and dtype."""

    @pytest.mark.parametrize("layout", ["flat", "leaf"])
    def test_fedavg_gates_padded_slots(self, layout):
        rng = np.random.default_rng(12)
        shape = (3000,) if layout == "flat" else (12, 250)
        dtype = jnp.float32 if layout == "flat" else jnp.bfloat16
        u = jnp.asarray(rng.normal(size=(5,) + shape), dtype)
        w = jnp.asarray([0.3, 0.2, 0.5, 0.7, 0.9], jnp.float32)
        act = jnp.asarray([True, True, True, False, False])
        agg, res = compress_merge_leaf(u, w, None, strategies.get("fedavg"),
                                       gamma=5.0, overlap_d=1, active=act)
        want = jnp.tensordot(w[:3], u[:3].astype(jnp.float32), axes=(0, 0))
        assert res is None and agg.shape == shape
        assert agg.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(agg), np.asarray(want))


class TestKForRatioHelpers:
    def test_traced_matches_host_grid(self):
        """The shared rounding rule: the traced twin must agree with the
        host `k_for_ratio` across n and CR grids (incl. CR=1 -> k=n and
        tiny CRs -> k=1)."""
        crs = np.concatenate([np.geomspace(1e-4, 1.0, 60),
                              [0.05, 0.1, 0.25, 0.5, 1.0]])
        for n in (1, 7, 100, 8192, 65536):
            host = np.array([k_for_ratio(n, float(c)) for c in crs])
            traced = np.asarray(
                k_for_ratio_traced(n, jnp.asarray(crs, jnp.float32)))
            np.testing.assert_array_equal(host, traced)
            assert traced.min() >= 1 and traced.max() <= n


class TestScheduleBatch:
    def test_rowwise_bit_exact_with_make_schedule(self):
        """The vectorized R-round schedule must equal per-round
        `make_schedule` over each round's active prefix, bit-for-bit,
        despite cohort-slot padding."""
        from repro.core.cost_model import sample_links
        links = sample_links(8, np.random.default_rng(1))
        r_n, c = 6, 5
        v_bytes = 4e6
        active = np.zeros((r_n, c), bool)
        bw = np.ones((r_n, c))
        lat = np.zeros((r_n, c))
        fr = np.zeros((r_n, c))
        sels = []
        rng = np.random.default_rng(3)
        for r in range(r_n):
            c_r = int(rng.integers(2, c + 1))
            sel = rng.choice(8, c_r, replace=False)
            sels.append(sel)
            active[r, :c_r] = True
            bw[r, :c_r] = [links[i].bandwidth_bps for i in sel]
            lat[r, :c_r] = [links[i].latency_s for i in sel]
            fr[r, :c_r] = rng.dirichlet(np.ones(c_r))
        crs_b, coef_b, tb = bcrs_mod.make_schedule_batch(
            bw, lat, fr, v_bytes, 0.05, 1.0, active=active)
        for r in range(r_n):
            c_r = int(active[r].sum())
            sched = bcrs_mod.make_schedule([links[i] for i in sels[r]],
                                           fr[r, :c_r], v_bytes, 0.05, 1.0)
            np.testing.assert_array_equal(sched.crs, crs_b[r, :c_r])
            np.testing.assert_array_equal(sched.coefficients,
                                          coef_b[r, :c_r])
            assert sched.t_bench == tb[r]
            assert (crs_b[r, c_r:] == 0).all()
            assert (coef_b[r, c_r:] == 0).all()


class TestFlTrainDriver:
    """End-to-end driver contract on a reduced real arch: engine parity,
    one compile per chunk shape, and bit-exact checkpoint/restart
    including the carried EF residual state."""

    BASE = dict(arch="stablelm-1.6b", reduced=True, clients=4,
                local_steps=1, batch=2, seq=16, cr=0.1, seed=5,
                verbose=False)

    def _run(self, **kw):
        from repro.launch.fl_train import FLTrainConfig, run
        return run(FLTrainConfig(**{**self.BASE, **kw}))

    def test_scan_matches_round_engine_under_faults(self):
        kw = dict(rounds=4, strategy="bcrs_opwa", fail_prob=0.25,
                  over_selection=0.5, participation=0.75,
                  checkpoint_every=2)
        key = ("mesh_scan", "bcrs_opwa")
        before = engine_mod.TRACE_COUNTS[key]
        scan = self._run(engine="scan", **kw)
        assert engine_mod.TRACE_COUNTS[key] - before == 1
        assert sum(scan["chunk_rounds"]) == len(scan["executed_rounds"])
        loop = self._run(engine="round", **kw)
        assert scan["executed_rounds"] == loop["executed_rounds"]
        np.testing.assert_array_equal(np.asarray(scan["losses"]),
                                      np.asarray(loop["losses"]))
        for a, b in zip(jax.tree.leaves(scan["params"]),
                        jax.tree.leaves(loop["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_trace_dir_holds_the_host_spans(self, tmp_path):
        """``trace_dir`` profiles the run: the written trace's host plane
        holds the scan loop's spans, on the device ops' timeline."""
        import glob
        from jax.profiler import ProfileData
        res = self._run(rounds=2, strategy="bcrs_opwa", checkpoint_every=1,
                        checkpoint_dir=str(tmp_path / "ckpt"),
                        trace_dir=str(tmp_path / "trace"))
        assert res["executed_rounds"] == [0, 1]
        files = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                          recursive=True)
        assert len(files) == 1
        host = [p for p in ProfileData.from_file(files[0]).planes
                if p.name == "/host:CPU"]
        assert len(host) == 1
        names = {e.name for line in host[0].lines for e in line.events}
        for span in ("fl.stage", "fl.compile", "fl.dispatch", "fl.wait",
                     "fl.account", "fl.checkpoint"):
            assert span in names, span

    def test_resumes_legacy_params_only_checkpoint(self, tmp_path):
        """A checkpoint from the pre-scan driver (bare params pytree, no
        'params/' prefix, no residual state) must actually LOAD — not
        silently fall back to fresh weights while skipping rounds."""
        from repro import checkpoint as ckpt
        ref = self._run(rounds=2, strategy="bcrs_opwa")
        ckpt.save(str(tmp_path), 2, ref["params"])   # legacy layout
        resumed = self._run(rounds=2, strategy="bcrs_opwa",
                            checkpoint_dir=str(tmp_path))
        assert resumed["resumed_from"] == 2
        assert resumed["executed_rounds"] == []      # nothing left to run
        # the returned params must be the RESTORED (trained) ones — a silent
        # no-match fallback would hand back the fresh init instead
        for a, b in zip(jax.tree.leaves(ref["params"]),
                        jax.tree.leaves(resumed["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_restore_rejects_unrelated_structure(self, tmp_path):
        """`strict=False` is for partial restores; a checkpoint sharing NO
        leaf with the requested structure is a layout mismatch and raises."""
        from repro import checkpoint as ckpt
        ckpt.save(str(tmp_path), 2, {"foo": np.zeros((3,), np.float32)})
        with pytest.raises(ckpt.LayoutMismatch, match="no leaves"):
            ckpt.restore(str(tmp_path), {"bar": np.zeros((3,), np.float32)},
                         strict=False)

    def test_restore_rejects_shape_drift(self, tmp_path):
        """A matching key with a drifted shape (e.g. EF residuals saved for
        a different cohort size) must fail at load with a named error, not
        later inside the compiled scan."""
        from repro import checkpoint as ckpt
        ckpt.save(str(tmp_path), 1, {"r": np.zeros((4, 3), np.float32)})
        with pytest.raises(ValueError, match="shape"):
            ckpt.restore(str(tmp_path), {"r": np.zeros((8, 3), np.float32)},
                         strict=False)

    def test_restart_bit_exact_with_residuals(self, tmp_path):
        kw = dict(strategy="eftopk", fail_prob=0.2, checkpoint_every=2)
        full = self._run(rounds=6, **kw)
        part = self._run(rounds=3, checkpoint_dir=str(tmp_path), **kw)
        assert part["resumed_from"] is None
        resumed = self._run(rounds=6, checkpoint_dir=str(tmp_path), **kw)
        assert resumed["resumed_from"] == 3
        assert (part["executed_rounds"] + resumed["executed_rounds"]
                == full["executed_rounds"])
        for a, b in zip(jax.tree.leaves(full["params"]),
                        jax.tree.leaves(resumed["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(full["residuals"]),
                        jax.tree.leaves(resumed["residuals"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
