"""Mesh-parallel federated training driver — the paper's system end-to-end:
clients on the batch mesh axes, BCRS per-round CR schedule, OPWA
aggregation, EF residual carrying, failure/straggler-aware cohorts,
checkpoint/restart — lowered into ONE compiled multi-round program.

The whole trajectory runs as ``engine.make_mesh_sim_scan``: the (possibly
TP/FSDP-sharded) params pytree and the per-leaf EF residual pytree thread
through a donated ``lax.scan`` carry, and everything the host decides per
round — cohort composition (``fed.simulation.plan_cohort``, the SAME
planner the simulation engines use), failure survivors, straggler arrivals,
and the BCRS schedule (``core.bcrs.make_schedule_batch``, one vectorized
call for all R rounds instead of one ``make_schedule`` per round) — is
precomputed as stacked ``[R, C]`` xs arrays. The scan is chunked at
checkpoint boundaries: one compile per distinct chunk length, one dispatch
per chunk, params + EF residuals persisted at every boundary
(``--engine round`` keeps the legacy one-jit-per-round dispatch loop as the
bit-parity reference).

All per-round randomness (synthetic client batches) is drawn from
round-indexed rng streams, so a resumed run consumes bit-identical data to
an uninterrupted one (tests/test_mesh_scan.py asserts restart bit-exactness
including the EF residual state).

    PYTHONPATH=src python -m repro.launch.fl_train --arch stablelm-1.6b \
        --reduced --rounds 10 --clients 8
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import ARCH_IDS, get_config
from repro.core import bcrs as bcrs_mod
from repro.core import cost_model
from repro.core import strategies as strat_mod
from repro.core.aggregation import AggregationConfig
from repro.data import synthetic_lm_tokens
from repro.fed import mesh_round as mesh_mod
from repro.fed import population as pop_mod
from repro.fed import engine as engine_mod
from repro.fed.mesh_round import make_mesh_round_step
from repro.fed.simulation import _link_columns, cohort_slots, plan_cohort
from repro.ft import FailureInjector, StragglerPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model

#: scan-chunk cap when no checkpoint cadence is configured — keeps the
#: device-resident per-chunk batch buffers O(MAX_CHUNK) instead of O(rounds)
MAX_CHUNK_ROUNDS = 32
#: default cadence when a checkpoint dir is set without --checkpoint-every:
#: bounded crash-loss window (the pre-scan driver saved every round; every
#: round would defeat the scan, 4 keeps the window small while amortizing)
DEFAULT_CHECKPOINT_EVERY = 4


@dataclass
class FLTrainConfig:
    """Everything the driver needs (the CLI below is a thin veneer)."""
    arch: str = "stablelm-1.6b"
    rounds: int = 10
    clients: int = 8
    participation: float = 1.0
    local_steps: int = 2
    batch: int = 4
    seq: int = 128
    strategy: str = "bcrs_opwa"
    cr: float = 0.05
    alpha: float = 1.0
    gamma: float = 3.0
    overlap_d: int = 1          # OPWA required degree of overlap D
    lr: float = 5e-2
    eta: float = 1.0
    reduced: bool = False
    fail_prob: float = 0.0
    over_selection: float = 0.0  # rho > 0 enables straggler over-selection
    checkpoint_dir: str = ""
    checkpoint_every: int = 0    # rounds per scan chunk; 0 = auto-capped
    engine: str = "scan"         # "scan" | "round" | "async"
    # ----------------- engine="async" (FedBuff buffered) knobs -----------
    async_buffer_k: int = 0      # 0 -> the cohort slot count
    async_concurrency: int = 0   # 0 -> min(2K, clients - K)
    async_alpha: float = 0.5     # staleness-discount exponent
    async_stall_s: float = float("inf")   # partial-flush deadline
    async_p_fail: float = 0.0    # per-attempt mid-transfer failure prob
    async_timeout_s: float = float("inf")
    async_version_ring: int = 8  # retained-version ring depth V (waves)
    async_batch_dispatch: bool = True   # False = per-dispatch baseline
    async_store_chunk: int = 4096       # sparse-store clients per chunk
    population: int = 0          # > 0: streaming-cohort mode over P clients
    cohort: int = 0              # cohort slots C (population mode; 0 ->
                                 # --clients is reused as the cohort size)
    use_kernel: object = "auto"
    seed: int = 0
    verbose: bool = True
    trace_dir: str = ""          # non-empty: profile the run into this dir

    def __post_init__(self):
        strat_mod.get(self.strategy)   # config-time error, names listed
        if self.population > 0:
            if self.cohort <= 0:
                self.cohort = self.clients
            if self.cohort > self.population:
                raise ValueError(
                    f"cohort {self.cohort} exceeds population "
                    f"{self.population}")

    @property
    def n_registered(self) -> int:
        """Registered client count: the population in streaming mode, the
        (dense-state) client count otherwise."""
        return self.population if self.population > 0 else self.clients

    @property
    def c_slots(self) -> int:
        """Static cohort slot count every padded plan array is sized with."""
        if self.population > 0:
            return self.cohort
        return cohort_slots(self.clients, self.participation)


@dataclass
class RoundPlan:
    """Host-precomputed per-round xs arrays for the executed rounds.

    Everything is padded to ``c_max`` cohort slots (active marks the real
    prefix) so every round shares one static shape; ``rounds`` holds the
    executed round numbers (rounds whose whole cohort died are absent — the
    scan carry is untouched by construction, matching the per-round
    engines' ``continue``)."""
    rounds: List[int]
    selected: np.ndarray     # [T, C] i32, -1 at padded slots
    active: np.ndarray       # [T, C] bool
    weights: np.ndarray      # [T, C] f32 (0 at padded slots)
    crs: np.ndarray          # [T, C] f32 (comm/compression ratio per client)
    step_mask: np.ndarray    # [T, C, S] bool


def _build_plan(cfg: FLTrainConfig, rng, fracs_all, links, v_bytes,
                acfg: AggregationConfig,
                failure: Optional[FailureInjector],
                straggler: Optional[StragglerPolicy]) -> RoundPlan:
    """Plan every round before training starts: cohorts through the shared
    ``plan_cohort`` (one rng stream, consumed in round order — restart-
    invariant because the whole plan is rebuilt identically at startup),
    then the BCRS schedule for ALL rounds in one vectorized
    ``make_schedule_batch`` call (the per-round ``make_schedule`` this
    replaces was loop-invariant whenever the cohort was).

    In population mode the same plan shape comes out, but every per-round
    quantity is O(C): the cohort is an absolute budget (``cfg.cohort``
    passed through ``plan_cohort``'s ``cohort=`` override), failure
    survivors are drawn per sampled id (``sparse_failures``), and the link
    columns are O(C) ``LinkArrays`` slices — the whole-run plan is
    O(rounds x C) regardless of P."""
    pop_mode = cfg.population > 0
    c_max = cfg.c_slots
    plans = []
    for rnd in range(cfg.rounds):
        p = plan_cohort(rnd, rng, n_clients=cfg.n_registered,
                        participation=cfg.participation, fracs_all=fracs_all,
                        links=links, v_bytes=v_bytes, acfg=acfg,
                        failure=failure, straggler=straggler,
                        cohort=cfg.cohort if pop_mode else None,
                        sparse_failures=pop_mode)
        if p is not None:
            plans.append((rnd, *p))
    t = len(plans)
    selected = np.full((t, c_max), -1, np.int32)
    active = np.zeros((t, c_max), bool)
    fr_pad = np.zeros((t, c_max), np.float64)
    # harmless placeholders at padded slots (they never reach the schedule
    # max or the merge: active gates them everywhere)
    bw = np.ones((t, c_max), np.float64)
    lat = np.zeros((t, c_max), np.float64)
    for i, (rnd, sel, fr) in enumerate(plans):
        c_r = len(sel)
        selected[i, :c_r] = sel
        active[i, :c_r] = True
        fr_pad[i, :c_r] = fr
        bw[i, :c_r], lat[i, :c_r] = _link_columns(links, sel)

    strat = strat_mod.get(cfg.strategy)
    if strat.weighting == "bcrs":
        crs, coeffs, _ = bcrs_mod.make_schedule_batch(
            bw, lat, fr_pad, v_bytes, cfg.cr, cfg.alpha, active=active)
        weights = coeffs.astype(np.float32)
        crs = crs.astype(np.float32)
    else:
        weights = fr_pad.astype(np.float32)
        # plan.crs are SELECTION ratios (they feed k_for_ratio_traced in the
        # round body); wire pricing is applied at accounting time
        cr_sel = cfg.cr if strat.compresses else 1.0
        crs = np.where(active, np.float32(cr_sel), np.float32(0.0))

    step_mask = np.zeros((t, c_max, cfg.local_steps), bool)
    step_mask[active] = True
    return RoundPlan(rounds=[p[0] for p in plans], selected=selected,
                     active=active, weights=weights, crs=crs,
                     step_mask=step_mask)


def _round_batches(cfg: FLTrainConfig, vocab: int, rnd: int,
                   c_max: int) -> Dict[str, np.ndarray]:
    """Synthetic LM batches for one round, drawn from a round-indexed rng
    stream — independent of resume point and of which earlier rounds were
    skipped, so checkpoint/restart consumes bit-identical data."""
    r = np.random.default_rng((cfg.seed, 104_729, rnd))
    toks = synthetic_lm_tokens(
        c_max * cfg.local_steps * cfg.batch, cfg.seq + 1, vocab, r).reshape(
            c_max, cfg.local_steps, cfg.batch, cfg.seq + 1)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


def _stack_batches(cfg: FLTrainConfig, vocab: int, rounds: List[int],
                   c_max: int) -> Dict[str, jax.Array]:
    per = [_round_batches(cfg, vocab, rnd, c_max) for rnd in rounds]
    return {k: jnp.asarray(np.stack([b[k] for b in per])) for k in per[0]}


def run(cfg: FLTrainConfig) -> dict:
    """Train per ``cfg``; returns {params, residuals, losses,
    executed_rounds, wall_per_round, chunk_rounds, compile_s, times,
    resumed_from} (``compile_s``: seconds spent compiling scan chunks).

    With ``cfg.trace_dir`` the run is profiled (``jax.profiler.trace``) into
    that directory: the device's ops, under the round body's scopes and the
    kernels' names, on one timeline with the host spans of the scan and
    round loops (``fl.stage``, ``fl.compile``, ``fl.dispatch``,
    ``fl.wait``, ``fl.account``, ``fl.checkpoint``)."""
    trace = (jax.profiler.trace(cfg.trace_dir) if cfg.trace_dir
             else contextlib.nullcontext())
    with trace:
        return _run(cfg)


def _run(cfg: FLTrainConfig) -> dict:
    model_cfg = get_config(cfg.arch)
    if cfg.reduced:
        model_cfg = model_cfg.reduced()
    model = Model(model_cfg)
    rng = np.random.default_rng(cfg.seed)
    params = model.init(jax.random.PRNGKey(cfg.seed))
    n_flat = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    v_bytes = 4.0 * n_flat
    c_max = cfg.c_slots
    strat = strat_mod.get(cfg.strategy)
    ef = strat.needs_residuals

    acfg = AggregationConfig(strategy=cfg.strategy, cr=cfg.cr,
                             alpha=cfg.alpha, gamma=cfg.gamma,
                             overlap_d=cfg.overlap_d,
                             use_kernel=cfg.use_kernel)
    if cfg.population > 0:
        # registry columns, not P Python objects: every per-round read
        # downstream is an O(C) slice
        links = cost_model.sample_link_arrays(cfg.population, rng)
    else:
        links = cost_model.sample_links(cfg.clients, rng)
    fracs_all = np.full(cfg.n_registered, 1.0 / cfg.n_registered)
    failure = (FailureInjector(p_fail=cfg.fail_prob, seed=cfg.seed)
               if cfg.fail_prob > 0 else None)
    straggler = (StragglerPolicy(over_selection=cfg.over_selection)
                 if cfg.over_selection > 0 else None)
    if cfg.engine == "async":
        return _run_async(cfg, model, model_cfg, params, links, strat,
                          acfg, fracs_all, n_flat, v_bytes)
    plan = _build_plan(cfg, rng, fracs_all, links, v_bytes, acfg,
                       failure, straggler)
    times = cost_model.TimeAccumulator()
    if cfg.population > 0:
        return _run_population(cfg, model, model_cfg, params, plan, links,
                               strat, n_flat, v_bytes, times)

    residuals = (engine_mod.init_mesh_residuals(params, c_max) if ef
                 else jnp.zeros((0,), jnp.float32))
    start, resumed_from = 0, None
    if cfg.checkpoint_dir and ckpt.latest_step(cfg.checkpoint_dir) is not None:
        like = {"params": params, "residuals": residuals}
        try:
            # strict=False: a residual-free checkpoint (e.g. strategy
            # switched to eftopk) resumes with fresh residuals
            tree, start, _extra = ckpt.restore(cfg.checkpoint_dir, like,
                                               strict=False)
            params, residuals = tree["params"], tree["residuals"]
        except ckpt.LayoutMismatch:
            # legacy layout: the pre-scan driver checkpointed the bare
            # params pytree at the top level (a shape-drifted leaf raises
            # plain ValueError above and must NOT reach this fallback)
            params, start, _extra = ckpt.restore(cfg.checkpoint_dir, params)
        resumed_from = start
        if cfg.verbose:
            print(f"[fl] resumed from round {start}")

    todo = [i for i, rnd in enumerate(plan.rounds) if rnd >= start]
    # checkpoint_every=0 still bounds the chunk: each chunk's batches are
    # materialized device-resident as xs, so an uncapped chunk would make a
    # long run O(rounds) in batch memory for zero benefit past the point
    # where dispatch overhead is amortized; with a checkpoint dir the
    # default cadence also bounds the crash-loss window
    if cfg.checkpoint_every > 0:
        chunk = cfg.checkpoint_every
    elif cfg.checkpoint_dir:
        chunk = DEFAULT_CHECKPOINT_EVERY
    else:
        chunk = min(max(len(todo), 1), MAX_CHUNK_ROUNDS)

    losses: List[float] = []
    wall_per_round: List[float] = []
    chunk_rounds: List[int] = []
    compile_s = 0.0
    kw = dict(strategy=cfg.strategy, eta=cfg.eta, gamma=cfg.gamma,
              overlap_d=cfg.overlap_d, use_kernel=cfg.use_kernel)

    def save(next_round: int) -> None:
        if cfg.checkpoint_dir:
            tree = {"params": params, "residuals": residuals}
            ckpt.save(cfg.checkpoint_dir, next_round, tree,
                      extra={"arch": cfg.arch, "strategy": cfg.strategy})

    def account_and_log(i: int, loss: float, wall: float) -> None:
        rnd = plan.rounds[i]
        sel = plan.selected[i][plan.active[i]]
        links_sel = [links[c] for c in sel]
        # selection CRs priced through the declared wire format (identity
        # for idx32+f32 strategies, dense 1.0 for fedavg — the driver's
        # legacy accounting — and honestly packed for e.g. qtopk)
        crs_wire = strat.wire.cr_eff(plan.crs[i][plan.active[i]], n_flat)
        times.add(cost_model.round_times(links_sel, v_bytes, crs_wire))
        losses.append(loss)
        wall_per_round.append(wall)
        if cfg.verbose:
            crs_act = plan.crs[i][plan.active[i]]
            print(f"[fl] round {rnd} loss {loss:.4f} "
                  f"cohort {len(sel)}/{cfg.clients} "
                  f"round_time {times.per_round[-1].actual:.2f}s "
                  f"CRs [{crs_act.min():.3f},{crs_act.max():.3f}]")

    # host spans: on the profiler's timeline beside the device's ops when
    # the run is traced, about a microsecond each when it is not
    span = jax.profiler.TraceAnnotation
    if cfg.engine == "scan":
        sim = engine_mod.make_mesh_sim_scan(model.loss_fn, params,
                                            lr=cfg.lr, **kw)
        compiled: Dict[int, object] = {}
        pos = 0
        while pos < len(todo):
            idx = todo[pos:pos + chunk]
            with span("fl.stage"):
                xs = {"batches": _stack_batches(
                          cfg, model_cfg.vocab_size,
                          [plan.rounds[i] for i in idx], c_max),
                      "step_mask": jnp.asarray(plan.step_mask[idx]),
                      "active": jnp.asarray(plan.active[idx]),
                      "weights": jnp.asarray(plan.weights[idx]),
                      "crs": jnp.asarray(plan.crs[idx])}
            # AOT-compile once per distinct chunk length; the jit cache
            # makes equal-length chunks ONE executable, so wall_per_round
            # reports steady-state dispatch cost
            if len(idx) not in compiled:
                t0 = time.perf_counter()
                with span("fl.compile"):
                    compiled[len(idx)] = sim.compile(params, residuals, xs)
                compile_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            with span("fl.dispatch"):
                out = compiled[len(idx)](params, residuals, xs)
            with span("fl.wait"):
                jax.block_until_ready(out["params"])
            wall = (time.perf_counter() - t0) / len(idx)
            params, residuals = out["params"], out["residuals"]
            with span("fl.account"):
                # one transfer for the chunk's losses, not one per round
                chunk_losses = jax.device_get(out["ys"]["loss"])
                for j, i in enumerate(idx):
                    account_and_log(i, float(chunk_losses[j]), wall)
            chunk_rounds.append(len(idx))
            with span("fl.checkpoint"):
                save(plan.rounds[idx[-1]] + 1)
            pos += len(idx)
    elif cfg.engine == "round":
        step = make_mesh_round_step(model.loss_fn, lr_local=cfg.lr, **kw)
        for pos, i in enumerate(todo):
            with span("fl.stage"):
                batches = {k: jnp.asarray(v) for k, v in _round_batches(
                    cfg, model_cfg.vocab_size, plan.rounds[i],
                    c_max).items()}
            t0 = time.perf_counter()
            with span("fl.dispatch"):
                params, residuals, loss = step(
                    params, residuals if ef else None, batches,
                    jnp.asarray(plan.step_mask[i]),
                    jnp.asarray(plan.weights[i]),
                    jnp.asarray(plan.crs[i]), jnp.asarray(plan.active[i]))
            with span("fl.wait"):
                jax.block_until_ready(params)
            wall = time.perf_counter() - t0
            if not ef:
                residuals = jnp.zeros((0,), jnp.float32)
            with span("fl.account"):
                account_and_log(i, float(loss), wall)
            chunk_rounds.append(1)
            if (pos + 1) % chunk == 0 or pos == len(todo) - 1:
                with span("fl.checkpoint"):
                    save(plan.rounds[i] + 1)
    else:
        raise ValueError(f"unknown engine {cfg.engine!r}")

    if cfg.verbose:
        print(f"[fl] done; accumulated comm time {times.actual:.1f}s "
              f"(straggler-free min would be {times.min:.1f}s)")
    return {"params": params, "residuals": residuals, "losses": losses,
            "executed_rounds": [plan.rounds[i] for i in todo],
            "wall_per_round": wall_per_round, "chunk_rounds": chunk_rounds,
            "compile_s": compile_s, "times": times,
            "resumed_from": resumed_from}


def _run_async(cfg: FLTrainConfig, model, model_cfg, params, links, strat,
               acfg: AggregationConfig, fracs_all, n_flat: int,
               v_bytes: float) -> dict:
    """FedBuff-style async buffered training on the real model: the
    simulation's ``fed.async_engine`` loop, in flat parameter space, with
    counter-keyed synthetic LM batches per dispatch (restart-invariant, like
    the sync driver's round-indexed streams). ``cfg.rounds`` counts buffer
    flushes; crash-safe state (params, per-client EF store, buffer,
    in-flight uploads) persists through ``cfg.checkpoint_dir`` and a rerun
    resumes bit-exactly.

    Dispatches batch into padded vmapped waves (one train-program jit call
    per wave shape bucket — docs/DESIGN.md §12) unless
    ``cfg.async_batch_dispatch`` is off. With ``cfg.population > 0`` the
    loop runs at streaming-population scale: O(C) cohort selection over P
    registered clients (``LinkArrays`` columns), per-client EF residuals in
    a sparse out-of-core ``population.ClientStateStore`` gathered only for
    the flushed buffer members, snapshotted chunk-wise through the
    checkpointer. Sharded (TP/FSDP) async is future work — this path trains
    single-device like the simulation engines."""
    from repro.core import aggregation as agg_mod
    from repro.core.compression import flatten_tree, k_for_ratio
    from repro.fed import async_engine as async_mod
    from repro.fed import population as pop_mod

    flat0, unravel = flatten_tree(params)
    times = cost_model.TimeAccumulator()
    n_reg = cfg.n_registered
    k_buf = cfg.async_buffer_k or cfg.c_slots
    m_conc = cfg.async_concurrency or max(1, min(2 * k_buf, n_reg - k_buf))
    fracs_norm = np.asarray(fracs_all, np.float64)
    fracs_norm = fracs_norm / fracs_norm.sum()
    if strat.weighting == "bcrs" and isinstance(links,
                                                cost_model.LinkArrays):
        # population mode: the vectorized whole-population schedule (no P
        # Python ClientLink objects — the _build_plan convention)
        crs_b, coeffs_b, _ = bcrs_mod.make_schedule_batch(
            links.bandwidth_bps[None], links.latency_s[None],
            fracs_norm[None], v_bytes, cfg.cr, cfg.alpha)
        crs_all, coeffs_all = crs_b[0], coeffs_b[0]
    else:
        crs_all, coeffs_all, _info = agg_mod.round_schedule(
            acfg, n_reg, fracs_norm, links, v_bytes)
    crs_arr = np.asarray(crs_all, np.float64)
    if strat.compresses and np.all(crs_arr == crs_arr.flat[0]):
        # uniform schedule (data weighting): one k, not P k_for_ratio calls
        ks_all = np.full((n_reg,),
                         k_for_ratio(n_flat, float(crs_arr.flat[0])),
                         np.int32)
    else:
        ks_all = agg_mod.ks_for_schedule(n_flat, crs_all)
    cr_eff_all = np.broadcast_to(np.asarray(
        strat.wire.cr_eff(crs_arr, n_flat), np.float64), (n_reg,))

    ef = strat.needs_residuals
    store = None
    if ef and cfg.population > 0:
        layout = strat.residual_layout
        width = (pop_mod.residual_width(n_flat, int(ks_all.min()))
                 if layout == "topk_complement" else 0)
        store = pop_mod.ClientStateStore(
            n_reg, n_flat, layout=layout, width=width,
            chunk_clients=min(cfg.async_store_chunk, n_reg))
        merge = async_mod.make_async_merge_step(
            acfg, eta=cfg.eta,
            residual_layout=("topk_complement"
                             if layout == "topk_complement" else "rows"),
            width=width)
    else:
        merge = async_mod.make_async_merge_step(acfg, eta=cfg.eta)

    wave_train = async_mod.make_wave_train_step(
        model.loss_fn, params, lr=cfg.lr,
        make_batches=lambda x: {"tokens": x["tokens"],
                                "labels": x["labels"]},
        strategy=cfg.strategy)
    smask_row = np.ones((cfg.local_steps,), bool)

    def batch_plan(client: int, uid: int) -> Dict[str, np.ndarray]:
        r = np.random.default_rng((cfg.seed, async_mod.BATCH_TAG, uid))
        toks = synthetic_lm_tokens(
            cfg.local_steps * cfg.batch, cfg.seq + 1, model_cfg.vocab_size,
            r).reshape(cfg.local_steps, cfg.batch, cfg.seq + 1)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:],
                "step_mask": smask_row}

    def on_flush(flush_idx: int, flat, rt: cost_model.RoundTime) -> None:
        times.add(rt)
        if cfg.verbose:
            print(f"[fl] flush {flush_idx} buffer {k_buf} "
                  f"interval {rt.actual:.2f}s slowest_upload {rt.max:.2f}s")

    def extra_state() -> dict:
        return {"times": [[float(t.actual), float(t.max), float(t.min)]
                          for t in times.per_round]}

    def load_extra(extra: dict) -> None:
        for a, mx, mn in extra.get("times", []):
            times.add(cost_model.RoundTime(a, mx, mn))

    ckpt_every = (cfg.checkpoint_every
                  or (DEFAULT_CHECKPOINT_EVERY if cfg.checkpoint_dir else 0))
    loop = async_mod.BufferedAsyncLoop(
        n_clients=n_reg, n_params=n_flat, buffer_k=k_buf,
        concurrency=m_conc, target_flushes=cfg.rounds, seed=cfg.seed,
        alpha=cfg.async_alpha, stall_s=cfg.async_stall_s,
        p_fail=cfg.async_p_fail,
        retry=cost_model.RetryPolicy(timeout_s=cfg.async_timeout_s),
        links=links, v_bytes=v_bytes, cr_eff_all=cr_eff_all, ks_all=ks_all,
        coeff_table=(coeffs_all if strat.weighting == "bcrs" else None),
        fracs_all=fracs_all, merge=merge, wave_train=wave_train,
        batch_plan=batch_plan, on_flush=on_flush,
        batch_dispatch=cfg.async_batch_dispatch,
        version_ring=cfg.async_version_ring, residual_store=store,
        checkpoint_dir=cfg.checkpoint_dir or None,
        checkpoint_every=ckpt_every, extra_state=extra_state,
        load_extra=load_extra)
    flat = loop.run(jnp.asarray(flat0))
    if cfg.verbose:
        print(f"[fl] done; accumulated virtual wall {times.actual:.1f}s "
              f"over {loop.flushes} flushes "
              f"({loop.train_calls} train dispatches / "
              f"{loop.train_rows} client updates)")
    return {"params": unravel(flat), "residuals": loop.store, "losses": [],
            "executed_rounds": list(range(loop.flushes)),
            "wall_per_round": [], "chunk_rounds": [], "times": times,
            "resumed_from": None, "async_loop": loop}


def _run_population(cfg: FLTrainConfig, model, model_cfg, params, plan,
                    links, strat, n_flat: int, v_bytes: float,
                    times) -> dict:
    """Streaming-cohort training over a population far larger than the
    cohort: per-client EF residuals live in a ``population.ClientStateStore``
    (sparse ``(idx32, f32)`` pairs for "topk_complement" strategies, chunked
    rows for "dense" ones) instead of a device-resident per-slot carry, and
    each round gathers just the sampled cohort's rows into the ONE compiled
    ``mesh_round.make_population_round_step`` program, scattering the
    updated rows back afterwards. Round state is O(C x n + touched-chunks),
    never O(P x n).

    Checkpoints persist ``{"params"}`` plus a per-step client-store snapshot
    (``clients_step_<N>/`` next to ``step_<N>.msgpack``, pruned in lockstep
    with the main retention), so a resumed run is bit-exact with an
    uninterrupted one including every client's residual."""
    ef = strat.needs_residuals
    layout = strat.residual_layout if ef else None
    c_max = cfg.c_slots
    if layout == "topk_complement":
        # every retained count the plan can emit bounds the residual nnz
        cr_min = (float(plan.crs[plan.active].min())
                  if plan.active.any() else cfg.cr)
        width = mesh_mod.mesh_residual_width(params, cr_min)
    else:
        width = 0

    store: Optional[pop_mod.ClientStateStore] = None
    start, resumed_from = 0, None
    if cfg.checkpoint_dir and ckpt.latest_step(cfg.checkpoint_dir) is not None:
        tree, start, extra = ckpt.restore(cfg.checkpoint_dir,
                                          {"params": params}, strict=False)
        params = tree["params"]
        man = (extra or {}).get("client_store")
        if ef and man is not None:
            if layout == "topk_complement" and man["width"] != width:
                raise ValueError(
                    f"client-store snapshot has sparse width {man['width']} "
                    f"but the rebuilt plan needs {width} — the plan (rounds/"
                    "cr/seed) changed across the restart")
            store = pop_mod.ClientStateStore.restore(
                cfg.checkpoint_dir, start, man,
                spill_dir=os.path.join(cfg.checkpoint_dir, "client_spill"))
        resumed_from = start
        if cfg.verbose:
            print(f"[fl] resumed from round {start} "
                  f"(population {cfg.population})")
    if ef and store is None:
        store = pop_mod.ClientStateStore(
            cfg.population, n_flat, layout=layout, width=width,
            chunk_clients=min(4096, cfg.population))

    step = mesh_mod.make_population_round_step(
        model.loss_fn, params, lr_local=cfg.lr, eta=cfg.eta,
        strategy=cfg.strategy, gamma=cfg.gamma, overlap_d=cfg.overlap_d,
        use_kernel=cfg.use_kernel, width=width)

    def save(next_round: int) -> None:
        if not cfg.checkpoint_dir:
            return
        extra = {"arch": cfg.arch, "strategy": cfg.strategy,
                 "population": cfg.population}
        if store is not None:
            extra["client_store"] = store.save(cfg.checkpoint_dir,
                                               next_round)
        ckpt.save(cfg.checkpoint_dir, next_round, {"params": params},
                  extra=extra)
        if store is not None:
            # retention just ran on the step files; drop the client
            # snapshots whose step it pruned
            pop_mod.prune_client_snapshots(
                cfg.checkpoint_dir, ckpt.list_steps(cfg.checkpoint_dir))

    todo = [i for i, rnd in enumerate(plan.rounds) if rnd >= start]
    if cfg.checkpoint_every > 0:
        chunk = cfg.checkpoint_every
    elif cfg.checkpoint_dir:
        chunk = DEFAULT_CHECKPOINT_EVERY
    else:
        chunk = max(len(todo), 1)
    losses: List[float] = []
    wall_per_round: List[float] = []
    zero_wire = jnp.zeros((0,), jnp.float32)   # carry="none" placeholder
    for pos, i in enumerate(todo):
        sel = plan.selected[i][plan.active[i]]
        c_r = len(sel)
        batches = {k: jnp.asarray(v) for k, v in _round_batches(
            cfg, model_cfg.vocab_size, plan.rounds[i], c_max).items()}
        if ef:
            gathered = store.gather(sel)
            bufs = []
            for a in gathered:      # zero-pad the cohort to the static slots
                buf = np.zeros((c_max,) + a.shape[1:], a.dtype)
                buf[:c_r] = a
                bufs.append(jnp.asarray(buf))
            wire = tuple(bufs) if layout == "topk_complement" else bufs[0]
        else:
            wire = zero_wire
        t0 = time.perf_counter()
        params, wire, loss, overflow = step(
            params, wire, batches, jnp.asarray(plan.step_mask[i]),
            jnp.asarray(plan.weights[i]), jnp.asarray(plan.crs[i]),
            jnp.asarray(plan.active[i]))
        loss = float(loss)          # blocks: wall includes the round
        wall = time.perf_counter() - t0
        if ef:
            if bool(overflow):
                raise RuntimeError(
                    f"round {plan.rounds[i]}: EF residual outgrew the "
                    f"sparse width {width}")
            arrays = wire if isinstance(wire, tuple) else (wire,)
            store.scatter(sel, tuple(np.asarray(a)[:c_r] for a in arrays))
        links_sel = [links[c] for c in sel]
        crs_wire = strat.wire.cr_eff(plan.crs[i][plan.active[i]], n_flat)
        times.add(cost_model.round_times(links_sel, v_bytes, crs_wire))
        losses.append(loss)
        wall_per_round.append(wall)
        if cfg.verbose:
            print(f"[fl] round {plan.rounds[i]} loss {loss:.4f} "
                  f"cohort {c_r}/{cfg.population} "
                  f"round_time {times.per_round[-1].actual:.2f}s")
        if (pos + 1) % chunk == 0 or pos == len(todo) - 1:
            save(plan.rounds[i] + 1)

    if cfg.verbose:
        print(f"[fl] done; accumulated comm time {times.actual:.1f}s "
              f"(straggler-free min would be {times.min:.1f}s)")
    return {"params": params, "residuals": store, "losses": losses,
            "executed_rounds": [plan.rounds[i] for i in todo],
            "wall_per_round": wall_per_round,
            "chunk_rounds": [1] * len(todo), "times": times,
            "resumed_from": resumed_from, "store": store}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm-1.6b")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--strategy", choices=strat_mod.names(),
                    default="bcrs_opwa")
    ap.add_argument("--cr", type=float, default=0.05)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--gamma", type=float, default=3.0)
    ap.add_argument("--overlap-d", type=int, default=1,
                    help="OPWA required degree of overlap D")
    ap.add_argument("--lr", type=float, default=5e-2)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--fail-prob", type=float, default=0.0)
    ap.add_argument("--over-selection", type=float, default=0.0,
                    help="straggler over-selection rho (0 disables)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="rounds per scan chunk / checkpoint cadence "
                         "(0 = auto chunking, checkpoint at chunk ends)")
    ap.add_argument("--engine", choices=("scan", "round", "async"),
                    default="scan")
    ap.add_argument("--async-buffer-k", type=int, default=0,
                    help="async merge buffer size K (0 = cohort slots)")
    ap.add_argument("--async-concurrency", type=int, default=0,
                    help="async in-flight dispatches M (0 = min(2K, N-K))")
    ap.add_argument("--async-alpha", type=float, default=0.5,
                    help="staleness-discount exponent")
    ap.add_argument("--async-stall", type=float, default=float("inf"),
                    help="partial-flush stall deadline in seconds")
    ap.add_argument("--async-p-fail", type=float, default=0.0,
                    help="per-attempt mid-transfer upload failure prob")
    ap.add_argument("--async-timeout", type=float, default=float("inf"),
                    help="per-upload hard deadline in seconds")
    ap.add_argument("--async-version-ring", type=int, default=8,
                    help="retained-parameter-version ring depth V for "
                         "batched wave dispatch")
    ap.add_argument("--async-sequential-dispatch", action="store_true",
                    help="disable batched wave dispatch (per-upload jit "
                         "baseline)")
    ap.add_argument("--population", type=int, default=0,
                    help="registered client count P for streaming-cohort "
                         "mode (0 = dense-state mode over --clients)")
    ap.add_argument("--cohort", type=int, default=0,
                    help="cohort slots C in population mode "
                         "(0 = reuse --clients)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default="",
                    help="profile the run into this directory (device ops "
                         "and the fl.* host spans; off when empty)")
    args = ap.parse_args()
    run(FLTrainConfig(
        arch=args.arch, rounds=args.rounds, clients=args.clients,
        participation=args.participation, local_steps=args.local_steps,
        batch=args.batch, seq=args.seq, strategy=args.strategy, cr=args.cr,
        alpha=args.alpha, gamma=args.gamma, overlap_d=args.overlap_d,
        lr=args.lr, reduced=args.reduced, fail_prob=args.fail_prob,
        over_selection=args.over_selection,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every, engine=args.engine,
        population=args.population, cohort=args.cohort,
        async_buffer_k=args.async_buffer_k,
        async_concurrency=args.async_concurrency,
        async_alpha=args.async_alpha, async_stall_s=args.async_stall,
        async_p_fail=args.async_p_fail, async_timeout_s=args.async_timeout,
        async_version_ring=args.async_version_ring,
        async_batch_dispatch=not args.async_sequential_dispatch,
        seed=args.seed, trace_dir=args.trace_dir))


if __name__ == "__main__":
    main()
