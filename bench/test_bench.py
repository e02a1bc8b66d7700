"""Tests of the benchmark harness, on the CPU at tiny sizes.

They check that every entry of BENCHMARK.json resolves to its files, that
the traffic copies draw what the program's generators draw, the trace
reduction on a synthetic trace, that a run off a TPU exits non-zero with no
result, and that a run whose timed path is broken, or is the float8
control, comes out not correct while a sound one comes out correct.
"""
from __future__ import annotations

import copy
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cells  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as traffic_mod  # noqa: E402

SPEC = cells.benchmark()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# ------------------------------------------------------------- resolution
def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert {m["name"] for m in SPEC["end_to_end"]} == {
        "round_s", "peak_hbm_gb", "setup_s"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    assert all(w["chips"] == 1 for w in SPEC["workloads"])
    for m in SPEC["per_layer"]:
        assert m["moves"] == "round_s"


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_resolves_by_name(name):
    cell = cells.resolve(name)
    w = {x["name"]: x for x in SPEC["workloads"]}[name]
    assert cell.config["name"] == w["config"]
    for fn in ("leaf_specs", "loss", "flops_per_token"):
        assert callable(getattr(cell.model_ref, fn))
    for key in ("strategy", "clients", "local_steps", "batch", "seq",
                "rounds_per_dispatch", "check_rounds", "lr", "eta"):
        assert key in cell.mix
    import run
    read = run.observed_rounds(cell.mix)
    assert read and read[-1] == cell.mix["check_rounds"]
    for name, lim in cell.limits.items():
        assert name.startswith(("change_", "loss_gap")) or (
            name.startswith("first_update_") and 1 in read), name
        assert lim["lower"] < lim["limit"] < lim["upper"], name
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    for reader in cell.readers.values():
        assert callable(reader.read)


def test_configs_name_their_cut():
    for c in SPEC["configs"]:
        cfg = cells.load_json(os.path.join(ROOT, c["file"]))
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert os.path.exists(os.path.join(ROOT, c["file"])[:-5] + ".py")


# ---------------------------------------------------------------- traffic
def test_tokens_match_the_program_generator():
    from repro.data import synthetic_lm_tokens
    vocab = 300
    want = synthetic_lm_tokens(6, 40, vocab, np.random.default_rng(5))
    got = traffic_mod.lm_tokens(6, 40, vocab, np.random.default_rng(5),
                                traffic_mod.zipf_cdf(vocab))
    np.testing.assert_array_equal(got, want)


def test_links_and_schedule_match_the_program():
    from repro.core import bcrs, cost_model
    la = cost_model.sample_link_arrays(7, np.random.default_rng(3))
    bw, lat = traffic_mod.sample_links(7, np.random.default_rng(3), 1.0, 0.2,
                                       0.05, 0.2)
    np.testing.assert_array_equal(bw, la.bandwidth_bps)
    np.testing.assert_array_equal(lat, la.latency_s)
    fr = np.full(7, 1 / 7)
    crs, coef, _ = bcrs.make_schedule_batch(bw[None], lat[None], fr[None],
                                            4e9, 0.05, 1.0)
    crs2, coef2 = traffic_mod.bcrs_schedule(bw, lat, fr, 4e9, 0.05, 1.0)
    np.testing.assert_array_equal(crs[0], crs2)
    np.testing.assert_array_equal(coef[0], coef2)


def test_traffic_is_a_function_of_the_seed():
    mix = cells.resolve(WORKLOADS[0]).mix
    a = traffic_mod.Traffic(mix, 500, 10_000, 2 ** 31 + 7).round(3)
    b = traffic_mod.Traffic(mix, 500, 10_000, 2 ** 31 + 7).round(3)
    c = traffic_mod.Traffic(mix, 500, 10_000, 2 ** 31 + 8).round(3)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].shape == (mix["clients"], mix["local_steps"],
                                 mix["batch"], mix["seq"])


@pytest.mark.parametrize("mix", ["merge", "train"])
def test_mix_agrees_with_the_program_strategy(mix):
    from repro.core import strategies
    m = cells.load_json(os.path.join(BENCH, "traffic", mix + ".json"))
    s = strategies.get(m["strategy"])
    assert s.compresses == (m["compress"] == "topk")
    assert s.overlap_weighted == bool(m["overlap_weighted"])
    assert s.needs_residuals == bool(m["error_feedback"])
    assert s.weighting == m["weighting"]


# ------------------------------------------------------------- reference
def test_kth_largest_is_exact():
    import jax.numpy as jnp
    from reference import kth_largest
    rng = np.random.default_rng(0)
    x = np.abs(rng.normal(size=5000)).astype(np.float32)
    x[:40] = x[40]                                   # ties
    for k in (1, 7, 41, 2500, 5000):
        want = np.sort(x)[::-1][k - 1]
        assert float(kth_largest(jnp.asarray(x), jnp.int32(k))) == want


def test_gap_is_taken_by_the_worst_leaf():
    from reference import compare
    p0 = {"a": np.zeros(4, np.float32), "b": np.zeros(4, np.float32),
          "c": np.ones(4, np.float32)}
    r1 = {"a": np.array([1, 0, 0, 0], np.float32),
          "b": np.array([2, 0, 0, 0], np.float32), "c": p0["c"]}
    r2 = {"a": np.array([2, 0, 0, 0], np.float32),
          "b": np.array([4, 0, 0, 0], np.float32), "c": p0["c"]}
    q1 = {"a": np.array([0, 1.1, 0, 0], np.float32), "b": r1["b"],
          "c": p0["c"] + 5}
    q2 = {"a": r2["a"], "b": np.array([3, 0, 0, 0], np.float32),
          "c": p0["c"] + 5}
    ref = {"losses": [2.0, 2.0], "states": [(1, r1), (2, r2)]}
    prog = {"losses": [2.02, 2.0], "states": [(1, q1), (2, q2)]}
    out = compare(prog, ref, p0)
    assert out["loss_gap"]["value"] == pytest.approx(0.01)
    # leaf c never moves in the reference: left out by the rule
    assert out["first_update_gap"]["leaf"] == "a"
    assert out["first_update_gap"]["value"] == pytest.approx(0.1 / 1.5)
    assert out["first_update_diff"]["value"] == pytest.approx(
        np.hypot(1, 1.1) / 1.5)
    # a moved one coordinate the reference did not, and kept not its one
    assert out["first_update_kept"]["value"] == pytest.approx(2.0)
    assert out["change_gap"]["value"] == pytest.approx(1.0 / 4.0)
    assert out["change_diff"]["leaf"] == "b"
    assert out["change_kept"]["value"] == 0.0


# ------------------------------------------------------------------ trace
HLO = """HloModule jit__run

%body.1 (arg: f32[4]) -> f32[4] {
  %dot.3 = f32[4]{0} dot(f32[4]{0} %a, f32[4]{0} %b), metadata={op_name="jit(_run)/while/body/closed_call/vmap()/while/body/closed_call/jvp()/dot_general"}
  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused.9
}

%fused.9 (p: f32[4]) -> f32[4] {
  %add.10 = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p), metadata={op_name="reduce_sum"}
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %while.5 = f32[4]{0} while(f32[4]{0} %x), condition=%cond.1, body=%body.1, metadata={op_name="jit(_run)/while/body/closed_call/vmap()/while"}
  %custom-call.6 = f32[4]{0} custom-call(f32[4]{0} %x), custom_call_target="tpu_custom_call", metadata={op_name="jit(_run)/while/body/closed_call/jit(megakernel_aggregate)/pallas_call"}
  %copy.7 = f32[4]{0} copy(f32[4]{0} %x)
}
"""


def test_hlo_name_stacks_split_the_layers():
    cat = trace_reduce.hlo_categories(HLO)
    assert cat["while.5"] == "local_train"
    assert cat["dot.3"] == "local_train"
    assert cat["fusion.4"] == "local_train"      # inherits its loop's stack
    assert cat["add.10"] == "local_train"        # and its fusion's
    assert cat["custom-call.6"] == "merge"
    assert cat["copy.7"] == "other"


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    ops = [("%while.5 = f32[4]{0} while(f32[4]{0} %x)", 10 * ms, 50 * ms),
           ("%dot.3 = f32[4]{0} dot(f32[4]{0} %a)", 12 * ms, 40 * ms),
           ("%custom-call.6 = u32[4,1]{1,0} custom-call(f32[4]{0} %x)",
            50 * ms, 90 * ms),
           ("%custom-call.6 = u32[4,1]{1,0} custom-call(f32[4]{0} %x)",
            120 * ms, 160 * ms)]
    host = [("bench.stage", 0, 10 * ms), ("bench.dispatch", 10 * ms, 91 * ms),
            ("bench.stage", 91 * ms, 120 * ms),
            ("bench.dispatch", 120 * ms, 200 * ms)]
    red = trace_reduce.reduce({"chips": [ops], "host": host},
                              trace_reduce.hlo_categories(HLO))
    assert red.window_s == pytest.approx(0.2)
    assert red.busy_s == pytest.approx(0.12)    # [10, 90] + [120, 160] ms
    assert red.layer_s["local_train"] == pytest.approx(0.040)  # 12 + 28
    assert red.layer_s["merge"] == pytest.approx(0.080)
    assert red.gaps[0] == ("bench.dispatch", pytest.approx(0.040))
    assert ("bench.stage", pytest.approx(0.030)) in red.gaps
    assert ("bench.stage", pytest.approx(0.010)) in red.gaps
    top = red.breakdown()["device_ops"][0]
    assert top[0].startswith("merge: custom-call.6 custom-call u32[4,1]")
    assert top[1] == pytest.approx(0.080)
    ctx = trace_reduce.Context(reduction=red, rounds=2,
                               peaks={"hbm_bytes_per_s": 1e9,
                                      "bf16_flops_per_s": 1e12},
                               model_flops_per_round=1e9,
                               merge_bytes_per_round=1e6)
    cell = cells.resolve(WORKLOADS[0])
    got = {k: r.read(ctx) for k, r in cell.readers.items()}
    assert got["local_train_s"] == pytest.approx(0.020)
    assert got["merge_s"] == pytest.approx(0.040)
    assert got["merge_roofline"] == pytest.approx(100 * 1e-3 / 0.040)
    assert got["round_mfu"] == pytest.approx(100 * 2e9 / 0.2 / 1e12)
    assert got["device_idle_share"] == pytest.approx(40.0)


# --------------------------------------------------------- off the chip
def _run_off_chip(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_off_a_tpu_exits_nonzero_with_no_result():
    p = _run_off_chip(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_a_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_off_chip(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


# ------------------------------------------------------ runs at tiny size
def tiny_cell(config: str, mix: str) -> cells.Cell:
    """A configuration (a file of bench/configs) under traffic ``mix`` (a
    file of bench/traffic), at a size the CPU runs in seconds, with the
    limits of the benchmark's cell of that configuration and mix."""
    path = os.path.join(BENCH, "configs", config)
    cell = cells.Cell(name=config + "." + mix, chips=1,
                      config=cells.load_json(path + ".json"),
                      model_ref=cells.load_module(path + ".py",
                                                  "tiny_" + mix),
                      mix=cells.load_json(os.path.join(BENCH, "traffic",
                                                       mix + ".json")),
                      limits=cells.resolve(f"{config}.{mix}").limits,
                      end_to_end=cells.resolve(WORKLOADS[0]).end_to_end)
    m = cell.config["model"]
    m.update(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
             d_ff=64, vocab_size=256)
    if cell.mix["local_steps"] == 1:
        # the CPU rounds a lone bfloat16 local step where the TPU keeps it
        # in float32; float32 storage makes the two the same
        m["dtype"] = "float32"
    # a small step keeps the tiny model's few local steps from diverging
    cell.mix.update(seq=16, lr=0.01,
                    local_steps=min(cell.mix["local_steps"], 2),
                    rounds_per_dispatch=min(cell.mix["rounds_per_dispatch"], 2),
                    check_rounds=min(cell.mix["check_rounds"], 2))
    return cell


#: (configuration, traffic) of every cell: the one-step merge path and the
#: several-local-steps dense path
TINY = [tuple(w.rsplit(".", 1)) for w in WORKLOADS]


def _tiny_run(cell, program=None):
    import jax
    import run
    kw = {"program": program} if program else {}
    return run.run_cell(cell, 7, 0.05, False, jax.devices()[0], 1,
                        {"hbm_bytes_per_s": 1.0, "bf16_flops_per_s": 1.0},
                        **kw)


@pytest.mark.parametrize("name,mix", TINY)
def test_a_sound_tiny_run_is_correct(name, mix):
    res = _tiny_run(tiny_cell(name, mix))
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"round_s", "peak_hbm_gb", "setup_s"}


@pytest.mark.parametrize("name,mix", TINY)
@pytest.mark.parametrize("fault", ["unchanged", "half_cohort", "half_batch"])
def test_a_broken_timed_path_is_not_correct(name, mix, fault):
    import readings
    program = (readings.KINDS[fault] if fault in readings.KINDS
               else readings.Faulty(fault))
    res = _tiny_run(tiny_cell(name, mix), program)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name,mix", TINY)
def test_the_float8_control_departs_farther_than_the_program(name, mix):
    import readings
    sound = _tiny_run(tiny_cell(name, mix))
    control = _tiny_run(tiny_cell(name, mix), readings.InPlace("fp8"))
    assert not control["correct"], control["check"]
    assert sound["correct"], sound["check"]
