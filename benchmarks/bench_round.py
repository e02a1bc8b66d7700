"""Round-engine benchmark: legacy per-client loop vs the fused jitted round,
plus the multi-round dimension (fused per-round dispatch vs the ONE-compile
``lax.scan`` simulation engine).

    PYTHONPATH=src python -m benchmarks.bench_round [--fast] [--out PATH]
    PYTHONPATH=src python -m benchmarks.bench_round --sim-scan [--fast]
    PYTHONPATH=src python -m benchmarks.bench_round --kernels [--fast]
    PYTHONPATH=src python -m benchmarks.bench_round --mesh-scan [--fast]
    PYTHONPATH=src python -m benchmarks.bench_round --async [--fast]

For each (strategy, cohort size K) cell it runs the same seeded simulation
through both engines, times steady-state rounds (first round excluded as
warmup/compile), counts XLA backend compilations via jax.monitoring, and
writes ``BENCH_round.json``:

    {"schema": "bench_round/v1",
     "env":    {"platform", "jax", "cpu_count"},
     "config": {"rounds", "warmup", "cr", "fast"},
     "results": [{"strategy", "clients",
                  "legacy": {"s_per_round", "s_per_round_min", "total_s",
                             "compiles"},
                  "fused":  {"s_per_round", "s_per_round_min", "total_s",
                             "compiles", "round_step_traces"},
                  "speedup", "accuracy_max_abs_diff"}, ...]}

``s_per_round`` is the median post-warmup wall time of one full round
(batch staging + local training + compression + aggregation + server
update; evaluation excluded); ``s_per_round_min`` the fastest such round.
``speedup`` = legacy min / fused min (scheduler noise only adds time, so
per-engine minima give the stable ratio on shared CI hardware).

``--sim-scan`` runs the multi-round benchmark instead and writes
``BENCH_sim_scan.json``: for each (strategy, rounds) cell it times the fused
per-round engine's steady-state round (median post-warmup wall) against the
scan engine's per-round execution cost — the scan path AOT-compiles the
whole trajectory, so wall/rounds of the compiled program excludes the
one-off compile exactly like the fused numbers exclude warmup. The model is
kept small so per-round *overhead* (Python dispatch, host staging), not
local SGD, dominates — the regime the scan lowering targets. Compile counts
must stay O(1) for both engines (recorded in the JSON). A ``ragged``
section records the step-cap (``FLSimConfig.step_cap_quantile``) win under
extreme Dirichlet skew.

``--mesh-scan`` benchmarks the REAL-MODEL mesh driver
(``repro.launch.fl_train``) and writes ``BENCH_mesh_scan.json``: for each
strategy it runs the same seeded reduced-arch training through the legacy
one-jit-per-round dispatch loop (``--engine round``, steady-state median
after warmup) and through the scanned multi-round program
(``--engine scan``, AOT-compiled chunk — wall/rounds of the executable, the
compile excluded exactly like the loop's warmup rounds), asserting the two
trajectories' losses agree bitwise and the scan traced exactly once.

``--kernels`` benchmarks the traced-k Pallas megakernel pipeline
(``threshold_find`` + ``fused_merge``) against the unfused jnp merge and
writes ``BENCH_kernels.json``: per (strategy, C, n) cell the roofline HBM
bytes of both lowerings (analytic kernel DMA model vs trip-count-aware HLO
accounting — repro.roofline.kernel_bytes), wall-clock (interpret mode off
TPU), a bit-exactness flag, and a trace-count assertion that the
kernel-routed scan simulation still compiles exactly once.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import strategies as strat_mod
from repro.core.aggregation import AggregationConfig
from repro.fed import round_step
from repro.fed.simulation import FLSimConfig, run_fl

STRATEGIES = ("fedavg", "eftopk", "bcrs_opwa")


class CompileCounter:
    """Counts XLA backend compilations via jax.monitoring duration events."""

    def __init__(self):
        self.n = 0
        self._active = False

    def _cb(self, name, duration, **kwargs):
        if self._active and "backend_compile" in name:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._cb)
        self._active = True
        return self

    def __exit__(self, *exc):
        # the gate above makes a leaked listener inert; the unregister hook
        # is private jax API, so treat it as best-effort
        self._active = False
        try:
            from jax._src import monitoring
            monitoring._unregister_event_duration_listener_by_callback(
                self._cb)
        except (ImportError, AttributeError):
            pass
        return False


BENCH_BETA = 20.0


def _sim_config(clients: int, rounds: int) -> FLSimConfig:
    # Full participation (cohort size == n_clients == K), ~96 samples and
    # one local batch per client per round: the paper's communication-bound
    # regime (large model, few local steps), where the round engine — not
    # local SGD — is the cost. beta=20 keeps Dirichlet label skew but
    # balanced enough that min_size=batch_size partitions sample quickly and
    # per-client step counts are comparable (extreme skew inflates the
    # fused path's padded-step waste; tracked as a ROADMAP open item).
    return FLSimConfig(n_clients=clients, participation=1.0, rounds=rounds,
                       n_train=96 * clients, n_test=600,
                       eval_every=10_000, seed=7, beta=BENCH_BETA)


def bench_cell(strategy: str, clients: int, rounds: int, warmup: int,
               cr: float) -> dict:
    acfg = AggregationConfig(strategy=strategy, cr=cr)
    sim = _sim_config(clients, rounds)
    out = {"strategy": strategy, "clients": clients}
    accs = {}
    for mode, fused in (("legacy", False), ("fused", True)):
        traces0 = sum(round_step.TRACE_COUNTS.values())
        with CompileCounter() as cc:
            t0 = time.perf_counter()
            res = run_fl(sim, acfg, fused=fused)
            total = time.perf_counter() - t0
        steady = res.wall_per_round[warmup:]
        out[mode] = {
            "s_per_round": statistics.median(steady),
            "s_per_round_min": min(steady),
            "total_s": total,
            "compiles": cc.n,
        }
        if fused:
            out[mode]["round_step_traces"] = (
                sum(round_step.TRACE_COUNTS.values()) - traces0)
        accs[mode] = np.array([a for _, a in res.accuracies])
    # ratio of fastest observed steady-state rounds (timeit-style: scheduler
    # noise only ever adds time, so min is the robust per-engine estimate)
    out["speedup"] = (out["legacy"]["s_per_round_min"]
                      / out["fused"]["s_per_round_min"])
    out["accuracy_max_abs_diff"] = float(
        np.abs(accs["legacy"] - accs["fused"]).max())
    return out


def run(fast: bool = False, rounds: int = 0, out_path: str = "BENCH_round.json"
        ) -> dict:
    ks = (8, 16) if fast else (8, 16, 32)
    rounds = rounds or (8 if fast else 12)
    warmup, cr = 2, 0.1
    if rounds <= warmup:
        raise SystemExit(f"--rounds must exceed the {warmup} warmup rounds")
    results = []
    for clients in ks:
        for strategy in STRATEGIES:
            cell = bench_cell(strategy, clients, rounds, warmup, cr)
            results.append(cell)
            print(f"{strategy:>10} K={clients:<3} "
                  f"legacy {cell['legacy']['s_per_round_min'] * 1e3:8.1f} "
                  f"ms/round ({cell['legacy']['compiles']:3d} compiles)  "
                  f"fused {cell['fused']['s_per_round_min'] * 1e3:8.1f} "
                  f"ms/round ({cell['fused']['compiles']:3d} compiles)  "
                  f"speedup {cell['speedup']:.2f}x  "
                  f"|dacc| {cell['accuracy_max_abs_diff']:.1e}")
    doc = {
        "schema": "bench_round/v1",
        "env": {"platform": jax.devices()[0].platform,
                "jax": jax.__version__,
                "cpu_count": os.cpu_count()},
        "config": {"rounds": rounds, "warmup": warmup, "cr": cr,
                   "beta": BENCH_BETA, "participation": 1.0,
                   "n_train_per_client": 96, "fast": fast},
        "results": results,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")
    return doc


# ------------------------------------------------------- multi-round (scan)
SCAN_STRATEGIES = ("bcrs_opwa", "eftopk")


def _scan_sim_config(clients: int, rounds: int, **kw) -> FLSimConfig:
    # dispatch-bound regime: tiny model + one local batch per client, so the
    # per-round engine overhead (Python loop, staging, dispatch) dominates
    # and the scan lowering's amortization is what gets measured
    base = dict(n_clients=clients, participation=1.0, rounds=rounds,
                dim=32, hidden=32, n_classes=10, batch_size=32,
                n_train=64 * clients, n_test=128, noise=3.0,
                eval_every=10_000, seed=7, beta=BENCH_BETA)
    base.update(kw)
    return FLSimConfig(**base)


def bench_scan_cell(strategy: str, clients: int, rounds: int,
                    warmup: int, cr: float) -> dict:
    """Fused steady-state ms/round vs the scan engine's per-round execution
    cost (``run_fl(engine="scan")`` AOT-compiles the trajectory and reports
    wall/rounds of the compiled program — the one-off compile is excluded
    exactly like the fused engine's discarded warmup rounds; the host plan
    build is reported separately as ``s_total``)."""
    from repro.fed import engine as engine_mod
    acfg = AggregationConfig(strategy=strategy, cr=cr)
    out = {"strategy": strategy, "clients": clients, "rounds": rounds}

    with CompileCounter() as cc:
        res_f = run_fl(_scan_sim_config(clients, rounds), acfg,
                       engine="fused")
    steady = res_f.wall_per_round[warmup:]
    out["fused"] = {"s_per_round": statistics.median(steady),
                    "s_per_round_min": min(steady),
                    "compiles": cc.n}

    traces0 = sum(engine_mod.TRACE_COUNTS.values())
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        res_s = run_fl(_scan_sim_config(clients, rounds), acfg,
                       engine="scan")
        total = time.perf_counter() - t0
    out["scan"] = {"s_per_round": res_s.wall_per_round[0],
                   "s_total": total, "compiles": cc.n,
                   "sim_traces": (sum(engine_mod.TRACE_COUNTS.values())
                                  - traces0)}
    out["dispatch_overhead_ratio"] = (out["fused"]["s_per_round"]
                                      / out["scan"]["s_per_round"])
    out["accuracy_max_abs_diff"] = float(np.abs(
        np.array([a for _, a in res_f.accuracies])
        - np.array([a for _, a in res_s.accuracies])).max())
    return out


def bench_ragged(fast: bool, quantile: float = 0.5) -> dict:
    """Step-cap datapoint: beta=0.1 Dirichlet skew makes the fused/scan
    engines pad every client to the cohort-max local step count; capping at
    the ``quantile`` of the per-client step distribution trades a little
    tail-client local work for a much tighter static shape."""
    from repro.fed.simulation import planned_client_steps
    rounds = 6 if fast else 10
    kw = dict(n_clients=8, participation=1.0, rounds=rounds, batch_size=32,
              n_train=2400, n_test=128, dim=64, hidden=64, n_classes=10,
              eval_every=10_000, seed=7, beta=0.1)
    acfg = AggregationConfig(strategy="bcrs_opwa", cr=0.1)
    out = {"beta": 0.1, "quantile": quantile, "rounds": rounds}
    for label, q in (("uncapped", 1.0), ("capped", quantile)):
        sim = FLSimConfig(**kw, step_cap_quantile=q)
        steps = planned_client_steps(sim)
        res = run_fl(sim, acfg, engine="fused")
        steady = res.wall_per_round[2:]
        out[label] = {
            "s_per_round": statistics.median(steady),
            "s_per_round_min": min(steady),
            "s_max_steps": int(steps.max()),
            "padded_step_frac": float(1.0 - steps.mean() / steps.max()),
        }
    out["speedup"] = (out["uncapped"]["s_per_round_min"]
                      / out["capped"]["s_per_round_min"])
    return out


def run_sim_scan(fast: bool = False,
                 out_path: str = "BENCH_sim_scan.json") -> dict:
    clients = 8
    rounds = 60 if fast else 120
    warmup, cr = 2, 0.1
    results = []
    for strategy in SCAN_STRATEGIES:
        cell = bench_scan_cell(strategy, clients, rounds, warmup, cr)
        results.append(cell)
        print(f"{strategy:>10} R={rounds:<4} "
              f"fused {cell['fused']['s_per_round'] * 1e3:7.2f} ms/round "
              f"({cell['fused']['compiles']:3d} compiles)  "
              f"scan {cell['scan']['s_per_round'] * 1e3:7.2f} "
              f"ms/round ({cell['scan']['sim_traces']} traces)  "
              f"overhead ratio {cell['dispatch_overhead_ratio']:.2f}x  "
              f"|dacc| {cell['accuracy_max_abs_diff']:.1e}")
    ragged = bench_ragged(fast)
    print(f"    ragged beta=0.1 cap@q{ragged['quantile']}: "
          f"{ragged['uncapped']['s_per_round_min'] * 1e3:.1f} -> "
          f"{ragged['capped']['s_per_round_min'] * 1e3:.1f} ms/round "
          f"({ragged['speedup']:.2f}x; padded frac "
          f"{ragged['uncapped']['padded_step_frac']:.2f} -> "
          f"{ragged['capped']['padded_step_frac']:.2f})")
    doc = {
        "schema": "bench_sim_scan/v1",
        "env": {"platform": jax.devices()[0].platform,
                "jax": jax.__version__,
                "cpu_count": os.cpu_count()},
        "config": {"clients": clients, "rounds": rounds,
                   "warmup": warmup, "cr": cr, "fast": fast},
        "results": results,
        "ragged": ragged,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")
    return doc


# ------------------------------------------------- real-model mesh driver
MESH_STRATEGIES = ("bcrs_opwa", "eftopk")


def bench_mesh_cell(strategy: str, rounds: int, warmup: int) -> dict:
    """One strategy through both fl_train engines on the same seeded
    reduced arch: legacy per-round-jit dispatch loop vs the scanned
    multi-round program (single AOT-compiled chunk, so its wall_per_round
    excludes the compile like the loop numbers exclude warmup)."""
    from repro.fed import engine as engine_mod
    from repro.launch.fl_train import FLTrainConfig, run as run_fl_train

    base = dict(arch="stablelm-1.6b", reduced=True, rounds=rounds,
                clients=4, local_steps=1, batch=2, seq=32,
                strategy=strategy, cr=0.1, seed=7, verbose=False)
    out = {"strategy": strategy, "rounds": rounds}

    with CompileCounter() as cc:
        res_r = run_fl_train(FLTrainConfig(**base, engine="round"))
    steady = res_r["wall_per_round"][warmup:]
    out["round"] = {"s_per_round": statistics.median(steady),
                    "s_per_round_min": min(steady),
                    "compiles": cc.n}

    key = ("mesh_scan", strategy)
    traces0 = engine_mod.TRACE_COUNTS[key]
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        res_s = run_fl_train(FLTrainConfig(**base, engine="scan"))
        total = time.perf_counter() - t0
    out["scan"] = {"s_per_round": res_s["wall_per_round"][0],
                   "s_total": total, "compiles": cc.n,
                   "mesh_scan_traces": engine_mod.TRACE_COUNTS[key] - traces0}
    out["dispatch_overhead_ratio"] = (out["round"]["s_per_round"]
                                      / out["scan"]["s_per_round"])
    out["loss_max_abs_diff"] = float(np.abs(
        np.array(res_r["losses"]) - np.array(res_s["losses"])).max())
    return out


def run_mesh_scan(fast: bool = False,
                  out_path: str = "BENCH_mesh_scan.json") -> dict:
    rounds = 8 if fast else 16
    warmup = 2
    results = []
    for strategy in MESH_STRATEGIES:
        cell = bench_mesh_cell(strategy, rounds, warmup)
        results.append(cell)
        print(f"{strategy:>10} R={rounds:<4} "
              f"round-loop {cell['round']['s_per_round'] * 1e3:7.1f} "
              f"ms/round ({cell['round']['compiles']:3d} compiles)  "
              f"scan {cell['scan']['s_per_round'] * 1e3:7.1f} ms/round "
              f"({cell['scan']['mesh_scan_traces']} traces)  "
              f"overhead ratio {cell['dispatch_overhead_ratio']:.2f}x  "
              f"|dloss| {cell['loss_max_abs_diff']:.1e}")
    doc = {
        "schema": "bench_mesh_scan/v1",
        "env": {"platform": jax.devices()[0].platform,
                "jax": jax.__version__,
                "cpu_count": os.cpu_count()},
        "config": {"rounds": rounds, "warmup": warmup,
                   "arch": "stablelm-1.6b-reduced", "clients": 4,
                   "fast": fast},
        "results": results,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")
    return doc


# ------------------------------------------------- megakernel pipeline
KERNEL_STRATEGIES = ("topk", "bcrs_opwa", "eftopk", "qtopk", "int4")
#: scanned-simulation 1-compile probes: the plain megakernel route and the
#: codec route
KERNEL_SCAN_STRATEGIES = ("bcrs_opwa", "qtopk")


def bench_kernels_cell(strategy: str, clients: int, n: int,
                       iters: int) -> dict:
    """One [C, n] merge through the unfused jnp ``compress_merge_leaf`` vs the
    traced-k Pallas megakernel pipeline: roofline HBM bytes (analytic DMA
    model vs trip-count-aware HLO accounting — see
    repro.roofline.kernel_bytes), wall-clock, and bit-exact parity.

    On non-TPU platforms the kernel route runs in Pallas INTERPRET mode, so
    its wall-clock is a correctness/overhead datapoint, not a hardware
    prediction — the roofline bytes are the portable win metric."""
    from repro.core.compression import k_for_ratio
    from repro.fed import engine as engine_mod
    from repro.roofline import merge_traffic_ratio, wire_stream_bytes

    rng = np.random.default_rng(clients * 7 + n % 1009)
    u = jnp.asarray(rng.normal(size=(clients, n)).astype(np.float32))
    e = jnp.asarray((rng.normal(size=(clients, n)) * 0.3).astype(np.float32))
    w = rng.random(clients).astype(np.float32) + 0.05
    w = jnp.asarray(w / w.sum())
    # BCRS-style spread of per-client retained counts
    crs = np.geomspace(0.01, 0.5, clients)
    ks = jnp.asarray([k_for_ratio(n, float(c)) for c in crs], jnp.int32)
    strat = strat_mod.get(strategy)
    ef = strat.needs_residuals

    platform = jax.devices()[0].platform
    out = {"strategy": strategy, "clients": clients, "n": n,
           # per-entry provenance: off-TPU the kernel route runs in Pallas
           # INTERPRET mode, so this cell's wall-clock must never be read
           # as a hardware comparison (--check warns on exactly this)
           "backend": platform, "interpret": platform != "tpu"}
    aggs = {}
    for label, use_kernel in (("unfused", False), ("kernel", True)):
        fn = jax.jit(lambda u, w, ks, e, use_kernel=use_kernel: engine_mod.
                     compress_merge_leaf(u, w, ks, strat, gamma=5.0,
                                         overlap_d=1, use_kernel=use_kernel,
                                         residuals=e if ef else None))
        agg, new_res = fn(u, w, ks, e)              # warmup/compile
        agg.block_until_ready()
        walls = []
        for _ in range(iters):
            t0 = time.perf_counter()
            a, r = fn(u, w, ks, e)
            a.block_until_ready()
            if r is not None:
                r.block_until_ready()
            walls.append(time.perf_counter() - t0)
        aggs[label] = (np.asarray(agg),
                       np.asarray(new_res) if ef else None)
        out[label] = {"s_per_merge": statistics.median(walls),
                      "s_per_merge_min": min(walls)}
    out["agg_max_abs_diff"] = float(
        np.abs(aggs["kernel"][0] - aggs["unfused"][0]).max())
    out["bit_exact"] = bool(
        (aggs["kernel"][0] == aggs["unfused"][0]).all()
        and (not ef or (aggs["kernel"][1] == aggs["unfused"][1]).all()))
    spec_ref = engine_mod.ClientUpdateSpec(strategy=strategy, gamma=5.0)
    out["roofline"] = merge_traffic_ratio(spec_ref, clients, n)
    # upload pricing of the cell's median per-client k under the strategy's
    # registered wire format (packed codecs beat the idx32+f32 reference
    # pair on the per-survivor stream: int8 5/8, int4 9/16)
    out["wire"] = wire_stream_bytes(strategy, n,
                                    int(np.median(np.asarray(ks))))
    return out


def run_kernels(fast: bool = False,
                out_path: str = "BENCH_kernels.json") -> dict:
    from repro.core.aggregation import AggregationConfig
    from repro.fed import engine as engine_mod
    from repro.fed.simulation import FLSimConfig, run_fl

    cells = ([(8, 1 << 13), (16, 1 << 14)] if fast
             else [(8, 1 << 14), (16, 1 << 16), (32, 1 << 16)])
    iters = 3 if fast else 5
    results = []
    for clients, n in cells:
        for strategy in KERNEL_STRATEGIES:
            cell = bench_kernels_cell(strategy, clients, n, iters)
            results.append(cell)
            r = cell["roofline"]
            print(f"{strategy:>10} C={clients:<3} n={n:<7} "
                  f"HBM {r['unfused']['passes']:6.1f} -> "
                  f"{r['kernel']['passes']:5.1f} passes "
                  f"({r['ratio']:.1f}x less traffic)  "
                  f"wall unfused {cell['unfused']['s_per_merge'] * 1e3:7.1f} "
                  f"ms  kernel {cell['kernel']['s_per_merge'] * 1e3:7.1f} ms"
                  f"  bit_exact={cell['bit_exact']}")

    # the kernel-routed scan simulation must still be ONE compile end to
    # end — for the plain megakernel route AND the codec route
    scan_traces = {}
    for scan_strat in KERNEL_SCAN_STRATEGIES:
        before = sum(engine_mod.TRACE_COUNTS.values())
        run_fl(FLSimConfig(rounds=4, n_clients=6, n_train=1200, n_test=300,
                           dim=32, hidden=32, n_classes=5, eval_every=2,
                           seed=2),
               AggregationConfig(strategy=scan_strat, cr=0.1,
                                 use_kernel=True),
               engine="scan")
        scan_traces[scan_strat] = (sum(engine_mod.TRACE_COUNTS.values())
                                   - before)
        print(f"kernel-routed scan simulation [{scan_strat}]: "
              f"{scan_traces[scan_strat]} trace(s)")

    doc = {
        "schema": "bench_kernels/v2",
        "env": {"platform": jax.devices()[0].platform,
                "jax": jax.__version__,
                "cpu_count": os.cpu_count(),
                "pallas_interpret": jax.devices()[0].platform != "tpu"},
        "config": {"iters": iters, "fast": fast,
                   "note": ("roofline bytes: analytic kernel DMA model vs "
                            "trip-count-aware HLO accounting of the unfused "
                            "lowering; wall-clock on non-TPU runs the "
                            "kernels in interpret mode")},
        "results": results,
        "scan_traces_with_kernels": scan_traces,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")
    return doc


# ------------------------------------------------- population-scale sweep
POPULATIONS_FULL = (1_000, 10_000, 100_000, 1_000_000)
POPULATIONS_FAST = (1_000, 10_000)
POP_STRATEGY = "eftopk"


def run_population(fast: bool = False,
                   out_path: str = "BENCH_population.json",
                   strategy: str = POP_STRATEGY) -> dict:
    """Streaming-cohort flatness sweep: the SAME compiled round program
    (``round_step.make_population_round_step``, reused across every P —
    TRACE_COUNTS must grow by exactly 1 over the whole sweep) driven over
    populations P = 10^3 .. 10^6 at a fixed cohort. The claim under test is
    the tentpole's: per-round wall-clock and peak host state bytes are flat
    in P, because every per-round quantity — cohort draw, gather/scatter,
    schedule, batch synthesis — is O(C), and the out-of-core store's LRU
    window bounds residency no matter how many clients have touched state.
    ``--fast`` sweeps the 10^3/10^4 points (CI); the committed artifact
    carries the full sweep."""
    import shutil
    import tempfile

    from repro.fed import population as pop_mod
    from repro.fed import round_step as rs_mod

    pops = POPULATIONS_FAST if fast else POPULATIONS_FULL
    rounds = 6 if fast else 10
    warmup, cohort, cr = 2, 16, 0.1
    # per-client chunks + a bounded LRU window: every round moves exactly
    # O(C) rows through the store no matter how many clients have touched
    # state, so BOTH wall-clock and peak residency are P-independent (a
    # multi-client chunk amortizes I/O when cohorts cluster, but at P >> C
    # each sampled id lands in its own chunk and the extra rows are pure
    # write amplification — the flatness sweep uses the honest worst case)
    chunk_clients, max_resident = 1, 2 * cohort
    acfg = AggregationConfig(strategy=strategy, cr=cr)
    traces0 = rs_mod.TRACE_COUNTS[("population", strategy)]
    step = None
    results = []
    for p in pops:
        t0 = time.perf_counter()
        pop = pop_mod.make_population(p, seed=3)
        registry_s = time.perf_counter() - t0
        cfg = pop_mod.PopulationRunConfig(cohort=cohort, rounds=rounds,
                                          seed=3)
        spill = tempfile.mkdtemp(prefix=f"bench_pop_{p}_")
        try:
            res, step, store = pop_mod.run_population_rounds(
                pop, cfg, acfg=acfg, step=step,
                chunk_clients=chunk_clients,
                max_resident_chunks=max_resident, spill_dir=spill)
        finally:
            shutil.rmtree(spill, ignore_errors=True)
        steady = res.wall_per_round[warmup:]
        total = sum(res.wall_per_round)
        cell = {
            "population": p,
            "s_per_round": statistics.median(steady),
            "s_per_round_min": min(steady),
            "registry_build_s": registry_s,
            "peak_state_bytes": int(res.peak_state_bytes),
            "gather_s": res.gather_seconds,
            "scatter_s": res.scatter_seconds,
            "gather_scatter_share": ((res.gather_seconds
                                      + res.scatter_seconds) / total),
            "chunk_loads": store.chunk_loads if store else 0,
            "chunk_spills": store.chunk_spills if store else 0,
            "final_loss": res.losses[-1],
        }
        results.append(cell)
        print(f"P={p:<8} {cell['s_per_round'] * 1e3:7.2f} ms/round "
              f"(min {cell['s_per_round_min'] * 1e3:6.2f})  "
              f"peak state {cell['peak_state_bytes'] / 1e6:7.1f} MB  "
              f"gather+scatter {cell['gather_scatter_share'] * 100:5.1f}%  "
              f"spills {cell['chunk_spills']}")
    traces = rs_mod.TRACE_COUNTS[("population", strategy)] - traces0
    base = results[0]
    for cell in results:
        cell["wall_ratio_vs_smallest"] = (cell["s_per_round"]
                                          / base["s_per_round"])
        cell["peak_ratio_vs_smallest"] = (cell["peak_state_bytes"]
                                          / base["peak_state_bytes"])
    print(f"population round program: {traces} trace(s) across the sweep")
    doc = {
        "schema": "bench_population/v1",
        "env": {"platform": jax.devices()[0].platform,
                "jax": jax.__version__,
                "cpu_count": os.cpu_count()},
        "config": {"strategy": strategy, "cohort": cohort, "rounds": rounds,
                   "warmup": warmup, "cr": cr,
                   "chunk_clients": chunk_clients,
                   "max_resident_chunks": max_resident, "fast": fast},
        "results": results,
        "population_traces": traces,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")
    return doc


# ------------------------------------------------------------ async engine
#: (label, bandwidth sd in Mbps around the 1.0 mean) — the floor clip at
#: 0.05 Mbps turns the high-sd draw into a long-tailed straggler mix
ASYNC_MIXES = (("mild", 0.2), ("extreme", 0.8))
ASYNC_STRATEGY = "eftopk"
ASYNC_PFAIL = 0.1


def _time_to_target(res, target: float) -> float:
    """Virtual seconds of simulated communication until the accuracy
    trajectory first crosses ``target`` (inf if it never does)."""
    cum = np.cumsum([t.actual for t in res.times.per_round])
    by_round = {r: i for i, r in enumerate(res.executed_rounds)}
    for r, acc in res.accuracies:
        if acc >= target:
            return float(cum[by_round[r]])
    return float("inf")


#: batched-dispatch probe shape: M in-flight per K-slot buffer — the
#: headline "jit dispatches per upload" configuration
ASYNC_PROBE_M = 32
ASYNC_PROBE_K = 8
#: async population sweep (reuses the sync sweep's P grid)
ASYNC_POP_FAST = POPULATIONS_FAST
ASYNC_POP_FULL = POPULATIONS_FULL


def run_async_dispatch_probe(fast: bool = False,
                             strategy: str = ASYNC_STRATEGY) -> dict:
    """Batched waves vs per-upload dispatch at M=32 in flight: the SAME
    seeded experiment runs with ``async_batch_dispatch`` on and off; the
    trajectories must be bit-identical (params + accuracies), and the
    batched run must issue >=3x fewer jit dispatches of the train program,
    compiling once per wave shape bucket (a small bounded set)."""
    from repro.fed import async_engine

    rounds = 6 if fast else 10
    base = dict(rounds=rounds, n_clients=64, participation=0.125,
                batch_size=8, beta=5.0, n_train=2048, n_test=400,
                dim=32, hidden=32, eval_every=2, seed=3,
                async_buffer_k=ASYNC_PROBE_K,
                async_concurrency=ASYNC_PROBE_M,
                async_p_fail_upload=ASYNC_PFAIL,
                async_upload_timeout_s=600.0)
    acfg = AggregationConfig(strategy=strategy, cr=0.05)
    key = ("async_train", strategy)
    t0 = async_engine.TRACE_COUNTS[key]
    res_b = run_fl(FLSimConfig(**base), acfg, engine="async")
    traces_batched = async_engine.TRACE_COUNTS[key] - t0
    t0 = async_engine.TRACE_COUNTS[key]
    res_s = run_fl(FLSimConfig(**base, async_batch_dispatch=False), acfg,
                   engine="async")
    traces_seq = async_engine.TRACE_COUNTS[key] - t0
    lb, ls = res_b.async_loop, res_s.async_loop
    bit_exact = bool(
        res_b.accuracies == res_s.accuracies
        and np.array_equal(np.asarray(lb.flat), np.asarray(ls.flat))
        and (res_b.final_residuals is None
             or np.array_equal(res_b.final_residuals,
                               res_s.final_residuals)))
    cell = {
        "strategy": strategy, "clients": base["n_clients"],
        "buffer_k": ASYNC_PROBE_K, "concurrency": ASYNC_PROBE_M,
        "rounds": rounds,
        "batched": {"train_calls": lb.train_calls,
                    "train_rows": lb.train_rows,
                    "train_traces": traces_batched,
                    "wave_buckets": sorted(lb.wave_buckets_used),
                    "forced_retires": lb.forced_retires,
                    "aborted_untrained": lb.aborted_untrained},
        "sequential": {"train_calls": ls.train_calls,
                       "train_rows": ls.train_rows,
                       "train_traces": traces_seq},
        "dispatch_ratio": ls.train_calls / lb.train_calls,
        "bit_exact": bit_exact,
    }
    print(f"dispatch M={ASYNC_PROBE_M}/K={ASYNC_PROBE_K}: "
          f"batched {lb.train_calls} train calls "
          f"({traces_batched} compiles, buckets "
          f"{sorted(lb.wave_buckets_used)}) vs sequential "
          f"{ls.train_calls} — {cell['dispatch_ratio']:.1f}x fewer, "
          f"bit_exact={bit_exact}")
    return cell


def run_async_population(fast: bool = False,
                         strategy: str = ASYNC_STRATEGY) -> list:
    """Async flatness sweep: the SAME compiled wave-train + merge programs
    driven by ``BufferedAsyncLoop`` over populations P = 10^3 .. 10^6 at a
    fixed buffer/concurrency. Per-flush wall-clock and peak host round
    state must be flat in P: O(1) rejection-sampled selection, O(K) sparse
    residual gather/scatter through a bounded-LRU ``ClientStateStore``, and
    the version ring replacing any P-sized parameter table."""
    import shutil
    import tempfile

    from repro.core import cost_model
    from repro.core.compression import flatten_tree, k_for_ratio
    from repro.fed import async_engine as ae
    from repro.fed import population as pop_mod
    from repro.fed import simulation as sim_mod

    pops = ASYNC_POP_FAST if fast else ASYNC_POP_FULL
    rounds = 12 if fast else 20
    warmup, k_buf, m_conc, cr = 2, 16, 32, 0.1
    acfg = AggregationConfig(strategy=strategy, cr=cr)
    dim, hidden, n_classes, bs, s_max, n_train = 16, 16, 5, 4, 2, 512
    params = sim_mod.mlp_init(jax.random.PRNGKey(3), dim, n_classes,
                              hidden=hidden)
    flat0, _ = flatten_tree(params)
    n_flat = int(flat0.shape[0])
    rngd = np.random.default_rng(7)
    x_all = jnp.asarray(rngd.normal(size=(n_train, dim)).astype(np.float32))
    y_all = jnp.asarray(rngd.integers(0, n_classes, n_train)
                        .astype(np.int32))
    k_ret = k_for_ratio(n_flat, cr)
    width = pop_mod.residual_width(n_flat, k_ret)
    # ONE pair of compiled programs reused across every P (their avals are
    # P-independent by construction — the jaxpr gate in tests asserts it)
    merge = ae.make_async_merge_step(acfg, residual_layout="topk_complement",
                                     width=width)
    wave_train = ae.make_wave_train_step(
        sim_mod.mlp_loss, params, lr=0.05,
        make_batches=lambda x: {"x": x_all[x["sample_idx"]],
                                "y": y_all[x["sample_idx"]]},
        strategy=strategy)

    def batch_plan(client: int, uid: int):
        r = np.random.default_rng((3, ae.BATCH_TAG, uid))
        return {"sample_idx": r.integers(n_train, size=(s_max, bs))
                .astype(np.int32),
                "step_mask": np.ones((s_max,), bool)}

    traces0 = ae.TRACE_COUNTS[("async_train", strategy)]
    cells = []
    for p in pops:
        t0 = time.perf_counter()
        pop = pop_mod.make_population(p, seed=3)
        registry_s = time.perf_counter() - t0
        spill = tempfile.mkdtemp(prefix=f"bench_async_pop_{p}_")
        marks = [time.perf_counter()]
        try:
            store = pop_mod.ClientStateStore(
                p, n_flat, layout="topk_complement", width=width,
                chunk_clients=1, max_resident_chunks=2 * k_buf,
                spill_dir=spill)
            loop = ae.BufferedAsyncLoop(
                n_clients=p, n_params=n_flat, buffer_k=k_buf,
                concurrency=m_conc, target_flushes=rounds, seed=3,
                alpha=0.5, stall_s=float("inf"), p_fail=ASYNC_PFAIL,
                retry=cost_model.RetryPolicy(timeout_s=600.0),
                links=pop.links, v_bytes=4.0 * n_flat,
                cr_eff_all=np.full(p, cr), ks_all=np.full(p, k_ret,
                                                          np.int32),
                coeff_table=None, fracs_all=pop.weights, merge=merge,
                wave_train=wave_train, batch_plan=batch_plan,
                residual_store=store,
                on_flush=lambda i, f, rt: marks.append(
                    time.perf_counter()))
            # fresh device copy per cell: the merge program donates its
            # params argument, so a shared flat0 would be consumed by the
            # first sweep point
            loop.run(jnp.array(flat0))
        finally:
            shutil.rmtree(spill, ignore_errors=True)
        per_flush = np.diff(marks)[warmup:]
        cell = {
            "population": p,
            "s_per_flush": float(statistics.median(per_flush)),
            "s_per_flush_min": float(per_flush.min()),
            "registry_build_s": registry_s,
            "peak_state_bytes": int(loop.peak_round_state_bytes),
            "train_calls": loop.train_calls,
            "wave_buckets": sorted(loop.wave_buckets_used),
            "chunk_loads": store.chunk_loads,
            "chunk_spills": store.chunk_spills,
        }
        cells.append(cell)
        print(f"P={p:<8} {cell['s_per_flush'] * 1e3:7.2f} ms/flush "
              f"(min {cell['s_per_flush_min'] * 1e3:6.2f})  "
              f"peak state {cell['peak_state_bytes'] / 1e6:7.2f} MB  "
              f"waves {loop.train_calls}  spills {store.chunk_spills}")
    base = cells[0]
    for cell in cells:
        # minima, not medians: at O(ms) flushes scheduler noise dominates
        # the median and only ever ADDS time (same convention as the
        # round-engine speedup at the top of this file)
        cell["wall_ratio_vs_smallest"] = (cell["s_per_flush_min"]
                                          / base["s_per_flush_min"])
        cell["peak_ratio_vs_smallest"] = (cell["peak_state_bytes"]
                                          / base["peak_state_bytes"])
    traces = ae.TRACE_COUNTS[("async_train", strategy)] - traces0
    print(f"async wave-train program: {traces} trace(s) across the sweep")
    return cells


def run_async_bench(fast: bool = False, out_path: str = "BENCH_async.json",
                    strategy: str = ASYNC_STRATEGY) -> dict:
    """Time-to-target-accuracy: synchronous deadline-drop vs async FedBuff.

    Per bandwidth mix, the same seeded experiment (dataset, partition,
    links, model init) runs through (a) the scan engine with the standard
    straggler mitigation — over-select, aggregate the first C·N arrivals,
    drop the rest at the deadline — plus round-level client failures, and
    (b) the async buffered engine with per-upload mid-transfer failures at
    the same rate. The metric is virtual communication time to reach 90%
    of the weaker run's best accuracy: the sync round is priced at the
    equalized-arrival duration of the aggregated set, the async flush at
    the event-loop time between flushes. The claim under test (the check
    gate): with a long-tailed bandwidth mix and failures, buffering K fast
    arrivals beats waiting on the deadline in >=1 mix.

    A ``chaos`` section smoke-tests the fault path at p_fail=0.6 with a
    tight per-upload timeout and a stall deadline (forced partial flushes):
    the run must complete every flush with ONE merge compile."""
    from repro.fed import async_engine
    from repro.ft.failures import FailureInjector
    from repro.ft.straggler import StragglerPolicy

    platform = jax.devices()[0].platform
    interpret = platform != "tpu"
    rounds = 12 if fast else 24
    # P=20 at 25% participation: the sync cohort is 5, and the async loop
    # over-provisions to M = min(2K, P - K) = 10 in flight per K=5-slot
    # buffer — the FedBuff regime (first K of M arrivals flush; a cohort-
    # sized population would pin M = K and the buffer would wait on its
    # slowest dispatch exactly like a sync round)
    # beta=5 keeps the Dirichlet partition mild: the heterogeneity under
    # test is the LINK mix, and min_size=batch must stay satisfiable for
    # 20 clients (beta=0.1 would resample forever at this n_train)
    # dataset size fixed across --fast: more data per client makes the MLP
    # converge inside async's pipeline-fill phase and the metric stops
    # resolving the steady state; the full mode only extends the horizon
    base = dict(rounds=rounds, n_clients=20, participation=0.25,
                batch_size=16, beta=5.0, n_train=2000, n_test=500,
                eval_every=1, seed=3)
    acfg = AggregationConfig(strategy=strategy, cr=0.05)
    results = []
    for label, bw_sd in ASYNC_MIXES:
        sim_sync = FLSimConfig(**base, link_bw_sd_mbps=bw_sd)
        res_sync = run_fl(sim_sync, acfg, engine="scan",
                          failure=FailureInjector(p_fail=ASYNC_PFAIL,
                                                  seed=base["seed"]),
                          straggler=StragglerPolicy())
        sim_async = FLSimConfig(**base, link_bw_sd_mbps=bw_sd,
                                async_p_fail_upload=ASYNC_PFAIL,
                                async_upload_timeout_s=600.0)
        res_async = run_fl(sim_async, acfg, engine="async")
        best_sync = max(a for _, a in res_sync.accuracies)
        best_async = max(a for _, a in res_async.accuracies)
        target = 0.9 * min(best_sync, best_async)
        t_sync = _time_to_target(res_sync, target)
        t_async = _time_to_target(res_async, target)
        cell = {
            "mix": label, "bw_sd_mbps": bw_sd, "p_fail": ASYNC_PFAIL,
            "backend": platform, "interpret": interpret,
            "target_accuracy": target,
            "sync": {"time_to_target_s": t_sync,
                     "total_comm_s": float(res_sync.times.actual),
                     "best_accuracy": best_sync},
            "async": {"time_to_target_s": t_async,
                      "total_comm_s": float(res_async.times.actual),
                      "best_accuracy": best_async},
            "speedup_time_to_target": t_sync / t_async,
        }
        results.append(cell)
        print(f"{label:<8} sd={bw_sd:.1f}  target {target:.3f}  "
              f"sync {t_sync:8.1f}s  async {t_async:8.1f}s  "
              f"speedup {cell['speedup_time_to_target']:.2f}x")

    # chaos smoke: heavy failures + tight timeout + stall deadline
    before = async_engine.TRACE_COUNTS[("async_merge", strategy)]
    sim_chaos = FLSimConfig(**base, link_bw_sd_mbps=0.8,
                            async_p_fail_upload=0.6, async_max_attempts=2,
                            async_upload_timeout_s=120.0,
                            async_stall_s=20.0)
    res_chaos = run_fl(sim_chaos, acfg, engine="async")
    durs = [t.actual for t in res_chaos.times.per_round]
    chaos = {
        "p_fail": 0.6, "max_attempts": 2, "timeout_s": 120.0,
        "stall_s": 20.0, "backend": platform, "interpret": interpret,
        "completed": len(res_chaos.executed_rounds) == rounds,
        "merge_traces": async_engine.TRACE_COUNTS[("async_merge", strategy)]
        - before,
        "flush_durations_nonnegative": bool(all(d >= 0 for d in durs)),
        "final_accuracy": res_chaos.final_accuracy,
    }
    print(f"chaos    p_fail=0.6 timeout=120s stall=20s: "
          f"{len(res_chaos.executed_rounds)}/{rounds} flushes, "
          f"{chaos['merge_traces']} merge trace(s), "
          f"acc {chaos['final_accuracy']:.3f}")

    print("-- batched dispatch probe --")
    dispatch = run_async_dispatch_probe(fast=fast, strategy=strategy)
    dispatch["backend"], dispatch["interpret"] = platform, interpret
    print("-- async population scaling --")
    population = run_async_population(fast=fast, strategy=strategy)
    for cell in population:
        cell["backend"], cell["interpret"] = platform, interpret

    doc = {
        "schema": "bench_async/v2",
        "env": {"platform": platform, "backend": platform,
                "interpret": interpret,
                "jax": jax.__version__,
                "cpu_count": os.cpu_count()},
        "config": {"strategy": strategy, "rounds": rounds, "cr": 0.05,
                   "p_fail": ASYNC_PFAIL, "fast": fast},
        "results": results,
        "chaos": chaos,
        "dispatch": dispatch,
        "population": population,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")
    return doc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="K in {8,16}, fewer rounds (CI-speed)")
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--out", default="BENCH_round.json")
    ap.add_argument("--strategy", default=None,
                    help="bench a single registered strategy instead of the "
                         "mode's default list (unknown names error, listing "
                         "what is registered)")
    ap.add_argument("--sim-scan", action="store_true",
                    help="run the multi-round benchmark (fused per-round "
                         "dispatch vs the one-compile scan engine) and "
                         "write BENCH_sim_scan.json")
    ap.add_argument("--mesh-scan", action="store_true",
                    help="benchmark the real-model mesh driver (scanned "
                         "multi-round program vs the legacy per-round-jit "
                         "loop) and write BENCH_mesh_scan.json")
    ap.add_argument("--kernels", action="store_true",
                    help="benchmark the traced-k Pallas megakernel pipeline "
                         "vs the unfused merge (roofline HBM bytes + "
                         "wall-clock + parity) and write BENCH_kernels.json")
    ap.add_argument("--async", dest="async_bench", action="store_true",
                    help="sync deadline-drop vs the async buffered engine "
                         "on time-to-target-accuracy over heterogeneous-"
                         "bandwidth mixes with upload failures, plus a "
                         "chaos smoke; writes BENCH_async.json")
    ap.add_argument("--population", action="store_true",
                    help="sweep the streaming-cohort engine over P = "
                         "10^3..10^6 registered clients (--fast: 10^3/10^4) "
                         "at a fixed cohort and write BENCH_population.json")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless fused beats legacy >=3x at "
                         "K=16 bcrs_opwa (with --sim-scan: scan dispatch "
                         "overhead >=2x lower than fused; with --kernels: "
                         "bit-exact, >=3x HBM traffic reduction, and a "
                         "1-compile kernel-routed scan; with --population: "
                         "wall-clock and peak state bytes <=1.25x the "
                         "smallest P, one compile across the sweep; with "
                         "--async: async wins time-to-target in >=1 mix "
                         "and the chaos run completes with 1 merge "
                         "compile)")
    args = ap.parse_args()
    if args.strategy is not None:
        global STRATEGIES, SCAN_STRATEGIES, MESH_STRATEGIES, KERNEL_STRATEGIES
        try:
            strat_mod.get(args.strategy)
        except ValueError as e:
            ap.error(str(e))
        only = (args.strategy,)
        STRATEGIES = SCAN_STRATEGIES = MESH_STRATEGIES = KERNEL_STRATEGIES = \
            only
    if args.async_bench:
        out = ("BENCH_async.json" if args.out == "BENCH_round.json"
               else args.out)
        strategy = args.strategy or ASYNC_STRATEGY
        doc = run_async_bench(fast=args.fast, out_path=out,
                              strategy=strategy)
        if args.check:
            if doc["env"]["interpret"]:
                print(f"WARNING: async cells ran on backend "
                      f"{doc['env']['backend']} (interpret-mode kernels) — "
                      "wall-clock columns are virtual-time/overhead "
                      "datapoints, not a hardware comparison; the check "
                      "gates only on event-stream invariants, dispatch "
                      "counts, and scaling ratios")
            wins = [c["mix"] for c in doc["results"]
                    if c["speedup_time_to_target"] > 1.0]
            ch = doc["chaos"]
            if (not wins or not ch["completed"] or ch["merge_traces"] != 1
                    or not ch["flush_durations_nonnegative"]):
                print(f"FAIL: async check (wins {wins}, chaos "
                      f"completed={ch['completed']} "
                      f"traces={ch['merge_traces']})")
                return 1
            dp = doc["dispatch"]
            if (dp["dispatch_ratio"] < 3.0 or not dp["bit_exact"]
                    or dp["batched"]["train_traces"]
                    != len(dp["batched"]["wave_buckets"])):
                print(f"FAIL: dispatch probe (ratio "
                      f"{dp['dispatch_ratio']:.2f}x, "
                      f"bit_exact={dp['bit_exact']}, "
                      f"traces={dp['batched']['train_traces']} vs buckets "
                      f"{dp['batched']['wave_buckets']})")
                return 1
            bad = [c for c in doc["population"]
                   if c["wall_ratio_vs_smallest"] > 1.25
                   or c["peak_ratio_vs_smallest"] > 1.25]
            if bad:
                print(f"FAIL: async population flatness "
                      f"(bad P {[c['population'] for c in bad]})")
                return 1
            pmax = doc["population"][-1]
            print(f"OK: async beats sync deadline-drop on time-to-target "
                  f"in {wins}; chaos run completed with 1 merge compile; "
                  f"batched dispatch {dp['dispatch_ratio']:.1f}x fewer "
                  f"train calls at M={ASYNC_PROBE_M} (bit-exact, "
                  f"{dp['batched']['train_traces']} compile(s)); async "
                  f"flat to P={pmax['population']} "
                  f"(wall {pmax['wall_ratio_vs_smallest']:.2f}x, peak "
                  f"state {pmax['peak_ratio_vs_smallest']:.2f}x)")
        return 0
    if args.population:
        out = ("BENCH_population.json" if args.out == "BENCH_round.json"
               else args.out)
        strategy = args.strategy or POP_STRATEGY
        doc = run_population(fast=args.fast, out_path=out, strategy=strategy)
        if args.check:
            bad = [c for c in doc["results"]
                   if c["wall_ratio_vs_smallest"] > 1.25
                   or c["peak_ratio_vs_smallest"] > 1.25]
            if bad or doc["population_traces"] != 1:
                print(f"FAIL: population flatness "
                      f"(bad P {[c['population'] for c in bad]}, "
                      f"traces {doc['population_traces']})")
                return 1
            pmax = doc["results"][-1]
            print(f"OK: flat to P={pmax['population']} "
                  f"(wall {pmax['wall_ratio_vs_smallest']:.2f}x, "
                  f"peak state {pmax['peak_ratio_vs_smallest']:.2f}x, "
                  "1 compile)")
        return 0
    if args.mesh_scan:
        out = ("BENCH_mesh_scan.json" if args.out == "BENCH_round.json"
               else args.out)
        doc = run_mesh_scan(fast=args.fast, out_path=out)
        if args.check:
            bad = [c for c in doc["results"]
                   if c["scan"]["mesh_scan_traces"] != 1
                   or c["loss_max_abs_diff"] != 0.0]
            if bad:
                print(f"FAIL: mesh-scan check "
                      f"{[c['strategy'] for c in bad]}")
                return 1
            print("OK: scanned mesh driver bit-exact with the per-round "
                  "loop, 1 trace per run")
        return 0
    if args.kernels:
        out = ("BENCH_kernels.json" if args.out == "BENCH_round.json"
               else args.out)
        doc = run_kernels(fast=args.fast, out_path=out)
        if args.check:
            interp = [c for c in doc["results"] if c.get("interpret")]
            if interp:
                print(f"WARNING: {len(interp)}/{len(doc['results'])} cells "
                      "ran the kernel route in Pallas interpret mode "
                      f"(backend {interp[0]['backend']}) — their wall-clock "
                      "columns are correctness/overhead datapoints, not a "
                      "hardware comparison; only the roofline bytes and "
                      "bit-exactness are checked")
            # packed codec wires must beat the idx32+f32 reference pair on
            # the per-survivor stream by their byte ratios
            wire_caps = {"qtopk": 5.0 / 8.0, "int4": 9.0 / 16.0}
            bad = [c for c in doc["results"]
                   if c["roofline"]["ratio"] < 3.0 or not c["bit_exact"]
                   or c["wire"]["pair_ratio"]
                   > wire_caps.get(c["strategy"], 1.0) + 1e-12]
            if bad or any(t != 1 for t in
                          doc["scan_traces_with_kernels"].values()):
                print(f"FAIL: kernels check "
                      f"(bad cells {[(c['strategy'], c['clients']) for c in bad]}, "
                      f"scan traces {doc['scan_traces_with_kernels']})")
                return 1
            print("OK: megakernel pipeline bit-exact (codec routes "
                  "included), >=3x HBM traffic reduction, packed wire "
                  "ratios within caps, 1-compile kernel-routed scans")
        return 0
    if args.sim_scan:
        out = ("BENCH_sim_scan.json" if args.out == "BENCH_round.json"
               else args.out)
        doc = run_sim_scan(fast=args.fast, out_path=out)
        if args.check:
            bad = [c for c in doc["results"]
                   if c["dispatch_overhead_ratio"] < 2.0]
            if bad:
                print(f"FAIL: dispatch overhead ratio < 2x in "
                      f"{[c['strategy'] for c in bad]}")
                return 1
            print("OK: scan dispatch overhead >=2x lower than fused")
        return 0
    doc = run(fast=args.fast, rounds=args.rounds, out_path=args.out)
    if args.check:
        cell = next(r for r in doc["results"]
                    if r["strategy"] == "bcrs_opwa" and r["clients"] == 16)
        if cell["speedup"] < 3.0:
            print(f"FAIL: bcrs_opwa K=16 speedup {cell['speedup']:.2f}x < 3x")
            return 1
        print(f"OK: bcrs_opwa K=16 speedup {cell['speedup']:.2f}x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
