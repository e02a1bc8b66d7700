"""End-to-end FL simulation harness (paper §5 experiment loop).

Reproduces the paper's protocol on synthetic Dirichlet-partitioned data with
a small MLP classifier (offline stand-in for ResNet18/CIFAR — validation
targets the paper's *relative* claims; see docs/DESIGN.md §7):

  for each round: sample C·N clients -> E local epochs SGD -> compress ->
  aggregate (fedavg | topk | eftopk | bcrs | bcrs_opwa) -> time accounting.

Three round engines (``engine`` / legacy ``fused`` flag):

  * fused (default): each round is ONE jitted program
    (repro.fed.round_step) — clients vmapped, traced-k compression, server
    update with donated buffers, batch staging double-buffered via
    ``device_put``. O(1) XLA compiles per simulation.
  * scan: the ENTIRE simulation is ONE jitted ``lax.scan`` over rounds
    (repro.fed.engine.make_sim_scan) — server flat params + EF residuals
    threaded as carry, host-precomputed cohort/schedule/batch-index arrays
    as xs, batches gathered in-jit. One compile, zero per-round dispatch;
    bit-compatible with the fused engine on the shared seeded rng stream.
  * legacy: the original per-client Python loop, kept as the parity
    reference (same rng stream, same schedules -> accuracies match the
    fused path within float-accumulation noise).

All engines draw cohort selection, failure survival, straggler arrivals, and
batch indices from ONE host rng stream in identical order, so their
trajectories are comparable point by point. ``run_fl_traced`` additionally
offers a fully in-jit sampling path (PRNG-key-driven masks instead of host
numpy — its own stream, not bit-parity with the host engines).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg_mod
from repro.core import bcrs as bcrs_mod
from repro.core import cost_model
from repro.core.opwa import overlap_counts
from repro.data import (build_client_datasets, data_fractions,
                        dirichlet_partition, synthetic_classification)
from repro.fed import engine as engine_mod
from repro.fed.client import make_local_trainer
from repro.fed.server import FLServer
from repro.ft import FailureInjector, StragglerPolicy, arrivals, over_select
from repro.ft.failures import survivors_traced
from repro.ft.straggler import (arrival_mask_traced,
                                renormalize_coefficients_traced)


# --------------------------------------------------------------- small model
def mlp_init(key, dim: int, n_classes: int, hidden: int = 128):
    k1, k2, k3 = jax.random.split(key, 3)
    s1, s2 = 1 / np.sqrt(dim), 1 / np.sqrt(hidden)
    return {
        "w1": jax.random.normal(k1, (dim, hidden)) * s1,
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, hidden)) * s2,
        "b2": jnp.zeros((hidden,)),
        "w3": jax.random.normal(k3, (hidden, n_classes)) * s2,
        "b3": jnp.zeros((n_classes,)),
    }


def mlp_loss(params, batch):
    x, y = batch["x"], batch["y"]
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    logits = h @ params["w3"] + params["b3"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None].astype(jnp.int32),
                                         axis=1))
    return loss, logits


@jax.jit
def mlp_accuracy(params, x, y):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    logits = h @ params["w3"] + params["b3"]
    return jnp.mean(jnp.argmax(logits, -1) == y)


# ------------------------------------------------------------------- harness
@dataclass
class FLSimConfig:
    """Defaults tuned (EXPERIMENTS.md §Repro) so that CR=0.01 Top-K visibly
    degrades accuracy — the regime where the paper's claims live."""
    n_clients: int = 10
    participation: float = 0.5        # C
    rounds: int = 40
    local_epochs: int = 1             # E
    batch_size: int = 64
    lr: float = 0.03                  # eta (local)
    beta: float = 0.1                 # Dirichlet heterogeneity
    n_train: int = 3000
    n_test: int = 1000
    n_classes: int = 20
    dim: int = 256
    hidden: int = 256
    noise: float = 3.0
    seed: int = 0
    eval_every: int = 5
    #: ragged-step mitigation: cap every client's local step count at this
    #: quantile of the per-client step distribution (1.0 = off). Under
    #: extreme Dirichlet skew the fused/scan engines pad every client to the
    #: cohort max (exact no-op steps, up to ~3x wasted compute at beta=0.1);
    #: trimming the tail trades a little local work of the largest clients
    #: for a much tighter static shape. Approximation knob — changes the
    #: trajectory, so parity suites leave it at 1.0.
    step_cap_quantile: float = 1.0
    # ------------------------- engine="async" (FedBuff buffered) knobs ----
    #: merge buffer size K (0 -> the synchronous cohort size C·N). In async
    #: mode ``rounds`` counts buffer FLUSHES, keeping trajectories and eval
    #: cadence comparable with the synchronous engines round-for-round
    async_buffer_k: int = 0
    #: in-flight upload concurrency M (0 -> min(2K, N - K), the FedBuff
    #: convention of over-provisioning dispatches vs the buffer)
    async_concurrency: int = 0
    #: staleness-discount exponent: w_i / (1 + s_i)^alpha (0 disables)
    async_alpha: float = 0.5
    #: partial-flush stall deadline (seconds of virtual time after the
    #: FIRST arrival into an empty buffer; inf = only flush when full)
    async_stall_s: float = float("inf")
    #: degenerate parity mode: replay the synchronous host round plans
    #: through the async train/merge programs (zero staleness by
    #: construction) — reproduces the scan engines' trajectories
    async_sync_arrivals: bool = False
    #: per-attempt mid-transfer upload failure probability; failed attempts
    #: resume from their byte offset after exponential backoff
    async_p_fail_upload: float = 0.0
    async_max_attempts: int = 3
    async_backoff_s: float = 0.5
    async_backoff_factor: float = 2.0
    #: hard wall-clock deadline per upload (seconds since dispatch)
    async_upload_timeout_s: float = float("inf")
    #: batched dispatch: record dispatches as pending and train them in
    #: padded vmapped WAVES at flush / ring-eviction / checkpoint time (one
    #: jit dispatch per wave shape bucket instead of one per upload) —
    #: bit-exact with per-client dispatch (False), which remains as the
    #: sequential baseline the dispatch benchmark compares against
    async_batch_dispatch: bool = True
    #: retained-parameter-version ring depth V for wave training. Must be
    #: >= the observable staleness bound (``async_engine.min_version_ring``:
    #: 1 when M <= K, else 2); deeper rings batch better under heavy
    #: staleness (shallow rings force-retire pending waves early, never
    #: affecting correctness)
    async_version_ring: int = 8
    #: opt back into the dense [P+1, n] EF residual reference store (the
    #: default is the sparse out-of-core ``population.ClientStateStore`` in
    #: the strategy's declared ``residual_layout``)
    async_dense_store: bool = False
    #: sparse-store chunking: clients per chunk
    async_store_chunk: int = 256
    #: sparse-store LRU bound: max resident chunks (0 = unbounded; bounding
    #: requires ``async_store_spill``)
    async_store_resident: int = 0
    #: directory evicted sparse-store chunks spill into ("" = none)
    async_store_spill: str = ""
    # ------------------------------------------- link population shape ----
    #: client uplink bandwidth distribution (normal, floored at 0.05 Mbps —
    #: ``cost_model.sample_link_arrays``). Defaults match the historical
    #: hard-coded draw, so seeded trajectories are unchanged; raising the sd
    #: produces the long-tailed heterogeneous-bandwidth mixes the async
    #: bench sweeps (benchmarks/bench_round.py --async)
    link_bw_mean_mbps: float = 1.0
    link_bw_sd_mbps: float = 0.2


@dataclass
class FLSimResult:
    accuracies: List[Tuple[int, float]] = field(default_factory=list)
    times: Optional[cost_model.TimeAccumulator] = None
    overlap_hist: Optional[np.ndarray] = None
    final_accuracy: float = 0.0
    wall_per_round: List[float] = field(default_factory=list)
    executed_rounds: List[int] = field(default_factory=list)
    #: final EF residuals [C, n] (eftopk only) — exposed so the scan engine's
    #: bit-parity with the fused engine is directly assertable
    final_residuals: Optional[np.ndarray] = None
    #: engine="async" only: the finished ``BufferedAsyncLoop`` (buffer /
    #: in-flight / counter state) — what the crash-restart bit-exactness
    #: tests compare against an uninterrupted run
    async_loop: Optional[object] = None

    def time_to_accuracy(self, target: float) -> Optional[float]:
        """Accumulated actual comm time up to AND INCLUDING the round whose
        evaluation first hits ``target`` (None if never reached).

        ``times.per_round[i]`` belongs to round ``executed_rounds[i]`` —
        rounds skipped by failure injection add no time entry, so the two
        lists are aligned by position, not by round number."""
        if self.times is None:
            return None
        per_round = self.times.per_round
        rounds_of = (self.executed_rounds
                     if len(self.executed_rounds) == len(per_round)
                     else list(range(len(per_round))))
        cum = 0.0
        i = 0
        for r, acc in self.accuracies:
            while i < len(per_round) and rounds_of[i] <= r:
                cum += per_round[i].actual
                i += 1
            if acc >= target:
                return cum
        return None


# ------------------------------------------------------------- shared setup
def _setup_sim(sim: FLSimConfig, acfg: agg_mod.AggregationConfig):
    """Seeded experiment setup shared by every entry point (run_fl and
    run_fl_traced MUST consume the host rng identically here, or 'same
    seed' stops meaning 'same dataset/links'). Returns
    (rng, clients, parts, fracs_all, splits, server)."""
    rng = np.random.default_rng(sim.seed)
    key = jax.random.PRNGKey(sim.seed)
    x, y = synthetic_classification(sim.n_train + sim.n_test, sim.n_classes,
                                    sim.dim, rng, noise=sim.noise)
    x_train, y_train = x[: sim.n_train], y[: sim.n_train]
    x_test, y_test = x[sim.n_train:], y[sim.n_train:]
    parts = dirichlet_partition(y_train, sim.n_clients, sim.beta, rng,
                                min_size=sim.batch_size)
    clients = build_client_datasets(x_train, y_train, parts)
    fracs_all = data_fractions(parts)
    params = mlp_init(key, sim.dim, sim.n_classes, hidden=sim.hidden)
    links = cost_model.sample_links(sim.n_clients, rng,
                                    bw_mean_mbps=sim.link_bw_mean_mbps,
                                    bw_sd_mbps=sim.link_bw_sd_mbps)
    server = FLServer(params=params, acfg=acfg, eta=1.0, links=links)
    return (rng, clients, parts, fracs_all,
            (x_train, y_train, x_test, y_test), server)


# ----------------------------------------------------------- host-side plan
def _client_steps(ds, sim: FLSimConfig) -> int:
    return max(1, (len(ds) // sim.batch_size)) * sim.local_epochs


def _steps_by_client(clients, sim: FLSimConfig) -> np.ndarray:
    """Per-client local step counts with the optional quantile cap applied
    (shared by every engine so the trajectories stay comparable)."""
    steps = np.array([_client_steps(ds, sim) for ds in clients], np.int64)
    if sim.step_cap_quantile < 1.0:
        cap = max(1, int(np.ceil(
            np.quantile(steps, sim.step_cap_quantile))))
        steps = np.minimum(steps, cap)
    return steps


def planned_client_steps(sim: FLSimConfig) -> np.ndarray:
    """Per-client local step counts (cap applied) for ``sim``'s seeded
    dataset — the exact partition every engine trains on, rebuilt through
    ``_setup_sim`` so reporting/benchmarks can't drift from the harness's
    rng draw order."""
    _, clients, *_ = _setup_sim(sim, agg_mod.AggregationConfig())
    return _steps_by_client(clients, sim)


def cohort_slots(n_clients: int, participation: float) -> int:
    """Target cohort size C·N — the ONE place the rounding rule lives.
    ``plan_cohort`` never emits a cohort larger than this, so it is also the
    static slot count every padded [rounds, C] plan array and EF residual
    buffer is sized with (fl_train, the scan engines)."""
    return max(1, int(round(n_clients * participation)))


def _link_columns(links, ids) -> Tuple[np.ndarray, np.ndarray]:
    """(bandwidth_bps, latency_s) float64 columns for the given client ids —
    an O(C) slice when ``links`` is a ``cost_model.LinkArrays`` (population
    scale), an O(C) comprehension over ``ClientLink`` objects otherwise.
    Either way the values are identical, so downstream vectorized math is
    bit-exact with the legacy per-object loops."""
    if isinstance(links, cost_model.LinkArrays):
        return links.bandwidth_bps[ids], links.latency_s[ids]
    return (np.array([links[c].bandwidth_bps for c in ids], np.float64),
            np.array([links[c].latency_s for c in ids], np.float64))


def plan_cohort(rnd: int, rng, *, n_clients: int, participation: float,
                fracs_all, links, v_bytes, acfg,
                failure: Optional[FailureInjector] = None,
                straggler: Optional[StragglerPolicy] = None,
                cohort: Optional[int] = None,
                sparse_failures: bool = False):
    """One round's cohort: selection -> failure survivors -> straggler
    arrivals -> renormalized weights. Shared by ALL engines — the three
    simulation engines AND the real-model mesh driver
    (``launch.fl_train``) — so failure/straggler planning has exactly one
    implementation; within the simulation harness the host rng stream is
    consumed in exactly this order everywhere, which is what makes
    legacy/fused/scan trajectories comparable. Returns (selected, fr) or
    None when the whole cohort died (the round is skipped).

    Population scale: pass ``cohort`` to fix the target size directly
    (instead of ``round(P * participation)`` — at P = 10^6 the cohort is an
    absolute budget, not a fraction) and ``sparse_failures=True`` to draw
    survivors per sampled id (``FailureInjector.survivors_at``, O(C)) rather
    than the dense ``[P]`` vector — its own seeded stream, and it revives a
    cohort member when all die, so the round is never skipped."""
    n_sel = cohort if cohort is not None \
        else cohort_slots(n_clients, participation)
    n_draw = over_select(n_sel, straggler) if straggler is not None else n_sel
    n_draw = min(n_draw, n_clients)
    selected = rng.choice(n_clients, n_draw, replace=False)
    if failure is not None:
        if sparse_failures:
            selected = selected[failure.survivors_at(rnd, selected)]
        else:
            alive = failure.survivors(rnd, n_clients)
            selected = selected[alive[selected]]
        if len(selected) == 0:
            return None
    if straggler is not None and len(selected) > n_sel:
        # completion times from the paper cost model at the configured CR,
        # priced through the strategy's declared wire format (dense -> 1.0,
        # the legacy fedavg convention; packed formats scale honestly).
        # Vectorized over the cohort (comm_time_batch is elementwise
        # bit-identical to the scalar loop) — O(C) numpy, no per-client
        # Python at any population size
        cr_eff = acfg.strat.wire.cr_eff(acfg.cr, int(v_bytes // 4))
        bw, lat = _link_columns(links, selected)
        t = bcrs_mod.comm_time_batch(v_bytes, bw, lat, cr_eff)
        chosen, _ = arrivals(t, n_sel, straggler)
        selected = selected[chosen]
    fr = fracs_all[selected]
    fr = fr / fr.sum()
    return selected, fr


def _plan_cohort(rnd: int, rng, sim: FLSimConfig, fracs_all, links, v_bytes,
                 acfg, failure: Optional[FailureInjector],
                 straggler: Optional[StragglerPolicy]):
    """FLSimConfig-flavored wrapper over ``plan_cohort`` for the simulation
    engines (same rng consumption, same return contract)."""
    return plan_cohort(rnd, rng, n_clients=sim.n_clients,
                       participation=sim.participation, fracs_all=fracs_all,
                       links=links, v_bytes=v_bytes, acfg=acfg,
                       failure=failure, straggler=straggler)


def _stack_client_batches(clients, selected, sim: FLSimConfig,
                          steps_by_client, s_max: int, rng
                          ) -> Tuple[dict, jax.Array]:
    """Draw each selected client's batches (same rng stream as the legacy
    loop), zero-pad to ``s_max`` steps, stack to [C, S, ...] + mask [C, S].

    Padded steps carry zeros and are masked to exact no-ops inside the
    fused trainer, so ragged step counts cost one static shape, not one
    recompile per cohort."""
    xs_all, ys_all = [], []
    mask = np.zeros((len(selected), s_max), bool)
    for j, c in enumerate(selected):
        ds = clients[c]
        steps = int(steps_by_client[c])
        xs, ys = ds.fixed_batches(sim.batch_size, steps, rng)
        if steps < s_max:
            xs = np.concatenate(
                [xs, np.zeros((s_max - steps,) + xs.shape[1:], xs.dtype)])
            ys = np.concatenate(
                [ys, np.zeros((s_max - steps,) + ys.shape[1:], ys.dtype)])
        xs_all.append(xs)
        ys_all.append(ys)
        mask[j, :steps] = True
    batches = {"x": np.stack(xs_all), "y": np.stack(ys_all)}
    return batches, mask


def _is_eval_round(sim: FLSimConfig, rnd: int) -> bool:
    """The ONE definition of the eval cadence — every engine's accuracy
    trajectory samples exactly these rounds."""
    return rnd % sim.eval_every == 0 or rnd == sim.rounds - 1


def _eval_plan(sim: FLSimConfig, rnds) -> Tuple[np.ndarray, np.ndarray]:
    """(eval_write bool [len(rnds)], eval_slot i32 [len(rnds)]) for the given
    executed round numbers — the scan engines' snapshot schedule."""
    write = np.array([_is_eval_round(sim, r) for r in rnds], bool)
    slot = np.zeros((len(write),), np.int32)
    slot[write] = np.arange(int(write.sum()), dtype=np.int32)
    return write, slot


def _overlap_hist(counts: np.ndarray, cohort_size: int) -> np.ndarray:
    """Fig. 4 binning shared by every engine: histogram of the nonzero
    degrees of overlap, padded to cohort_size+1 bins (degree 0 dropped)."""
    counts = np.asarray(counts)
    return np.bincount(counts[counts > 0], minlength=cohort_size + 1)


# ------------------------------------------------------------------ run_fl
def run_fl(sim: FLSimConfig, acfg: agg_mod.AggregationConfig,
           failure: Optional[FailureInjector] = None,
           collect_overlap: bool = False, fused: bool = True,
           engine: Optional[str] = None,
           straggler: Optional[StragglerPolicy] = None,
           checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
           stop_after: Optional[int] = None) -> FLSimResult:
    """Run the simulation. ``engine`` selects the round engine
    ("legacy" | "fused" | "scan" | "pop_scan" | "population" | "async");
    when None it falls back to the legacy ``fused`` bool
    ("fused" / "legacy").

    ``engine="async"`` is the FedBuff-style buffered engine
    (``fed.async_engine``): ``sim.rounds`` counts buffer flushes, the
    ``sim.async_*`` knobs shape the buffer/arrival process, and
    ``checkpoint_dir`` / ``checkpoint_every`` (flushes) enable crash-safe
    state persistence — a rerun with the same config resumes bit-exactly
    from the newest intact checkpoint. ``stop_after`` aborts after that
    many flushes (test hook simulating a crash at a flush boundary).

    The two population engines treat ``sim.n_clients`` as the registered
    population P and carry EF residuals PER CLIENT (state survives cohort
    resizes — no reset-on-resize): "pop_scan" keeps them in a dense
    ``[P + 1, n]`` scan carry (the small-P reference), "population" streams
    each round's cohort through a sparse out-of-core
    ``population.ClientStateStore`` (round state O(C x n + P x (n - k_min)),
    bit-exact with pop_scan)."""
    if engine is None:
        engine = "fused" if fused else "legacy"
    if engine not in ("legacy", "fused", "scan", "pop_scan", "population",
                      "async"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine != "async" and (checkpoint_dir is not None
                              or stop_after is not None):
        raise ValueError("checkpoint_dir / stop_after are engine='async' "
                         "features (the sync checkpointing entry point is "
                         "launch.fl_train)")
    (rng, clients, parts, fracs_all,
     (x_train, y_train, x_test, y_test), server) = _setup_sim(sim, acfg)
    links = server.links
    steps_by_client = _steps_by_client(clients, sim)
    s_max = int(steps_by_client.max())

    if engine in ("scan", "pop_scan"):
        return _run_scan(sim, acfg, rng, clients, parts, fracs_all, links,
                         server, steps_by_client, s_max, x_train, y_train,
                         x_test, y_test, failure, straggler, collect_overlap,
                         per_client_ef=(engine == "pop_scan"))
    if engine == "async":
        if collect_overlap:
            raise ValueError("the async engine does not carry the Fig. 4 "
                             "overlap instrumentation — use engine='scan'")
        from repro.fed.async_engine import run_async_sim
        return run_async_sim(sim, acfg, rng, clients, parts, fracs_all,
                             links, server, steps_by_client, s_max, x_train,
                             y_train, x_test, y_test, failure, straggler,
                             checkpoint_dir=checkpoint_dir,
                             checkpoint_every=checkpoint_every,
                             stop_after=stop_after)
    if engine == "population":
        if collect_overlap:
            raise ValueError("the population engine does not carry the "
                             "Fig. 4 overlap instrumentation — use "
                             "engine='scan' or 'pop_scan'")
        return _run_population(sim, acfg, rng, clients, parts, fracs_all,
                               links, server, steps_by_client, s_max,
                               x_train, y_train, x_test, y_test, failure,
                               straggler)

    if engine == "fused":
        server.init_fused(mlp_loss, sim.lr, collect_overlap=collect_overlap)
    else:
        local_train = jax.jit(make_local_trainer(mlp_loss, sim.lr))

    result = FLSimResult()
    overlap_hists = []

    def round_stream():
        """Per-round plans; for the fused engine the stacked client batches
        are staged to device here (async ``jnp.asarray`` transfer) so the
        consumer can pull round r+1 — staging its buffers — while round r's
        dispatched program is still running: double-buffered staging, two
        rounds' batch buffers alive at once, each consumed exactly once.
        The legacy engine draws its batches in the consumer, so it must not
        be prefetched (the shared rng stream would reorder)."""
        for rnd in range(sim.rounds):
            plan = _plan_cohort(rnd, rng, sim, fracs_all, links,
                                server.v_bytes, acfg, failure, straggler)
            if plan is None:
                continue
            selected, fr = plan
            staged = None
            if engine == "fused":
                batches, mask = _stack_client_batches(
                    clients, selected, sim, steps_by_client, s_max, rng)
                staged = ({k: jnp.asarray(v) for k, v in batches.items()},
                          jnp.asarray(mask))
            yield rnd, selected, fr, staged

    stream = round_stream()
    item = next(stream, None)
    while item is not None:
        rnd, selected, fr, staged = item
        t0 = time.perf_counter()
        is_overlap_round = collect_overlap and rnd == sim.rounds // 2

        if engine == "fused":
            batches, step_mask = staged
            info = server.round_fused(batches, step_mask, fr, selected,
                                      want_overlap=is_overlap_round)
            # prefetch: stage the NEXT round's buffers while this round's
            # dispatched program is still running on device
            item = next(stream, None)
            if is_overlap_round:
                overlap_hists.append(_overlap_hist(info["overlap_counts"],
                                                   len(selected)))
        else:
            deltas = []
            for c in selected:
                ds = clients[c]
                steps = int(steps_by_client[c])
                xs, ys = ds.fixed_batches(sim.batch_size, steps, rng)
                delta, _ = local_train(
                    server.params,
                    {"x": jnp.asarray(xs), "y": jnp.asarray(ys)})
                deltas.append(delta)
            info = server.round(deltas, fr, selected)

            if is_overlap_round:
                # reproduce Fig. 4: histogram of retained-parameter overlap
                from repro.core.compression import flatten_tree, topk_compress
                flat = jnp.stack([flatten_tree(d)[0] for d in deltas])
                crs = info.get("crs", np.full(len(deltas), acfg.cr))
                masks = jnp.stack([
                    topk_compress(flat[i], float(crs[i])).mask
                    for i in range(flat.shape[0])])
                overlap_hists.append(_overlap_hist(
                    np.asarray(overlap_counts(masks)), len(deltas)))
            item = next(stream, None)

        server._flat.block_until_ready()
        result.wall_per_round.append(time.perf_counter() - t0)
        result.executed_rounds.append(rnd)

        if _is_eval_round(sim, rnd):
            acc = float(mlp_accuracy(server.params, jnp.asarray(x_test),
                                     jnp.asarray(y_test)))
            result.accuracies.append((rnd, acc))

    result.times = server.times
    result.final_accuracy = result.accuracies[-1][1] if result.accuracies else 0.0
    if acfg.strat.needs_residuals and server._residuals is not None:
        result.final_residuals = np.asarray(server._residuals)
    if overlap_hists:
        result.overlap_hist = overlap_hists[0]
    return result


# ------------------------------------------------------- shared round plans
def _plan_rounds(sim, acfg, rng, clients, parts, fracs_all, links, server,
                 steps_by_client, s_max, failure, straggler,
                 collect_overlap) -> list:
    """Precompute every executed round's plan on the host (ONE rng stream,
    consumed in exactly the order the fused loop does): cohort -> BCRS
    schedule -> retained counts -> batch sample indices, with comm time
    accounted into ``server.times`` as it goes. Shared verbatim by the scan
    engine and both population engines, so their trajectories and comm
    accounting are identical by construction.

    Returns [(rnd, selected, weights, ks, ks_overlap, idx)]."""
    n_params, v_bytes = server.n_params, server.v_bytes
    bs = sim.batch_size
    plans = []
    for rnd in range(sim.rounds):
        plan = _plan_cohort(rnd, rng, sim, fracs_all, links, v_bytes, acfg,
                            failure, straggler)
        if plan is None:
            continue
        selected, fr = plan
        c_r = len(selected)
        links_sel = [links[i] for i in selected]
        crs, weights, info = agg_mod.round_schedule(acfg, c_r, fr, links_sel,
                                                    v_bytes)
        ks = agg_mod.ks_for_schedule(n_params, crs)
        ks_overlap = (agg_mod.overlap_ks(acfg, info, c_r, n_params)
                      if collect_overlap and rnd == sim.rounds // 2
                      else None)
        # batch sample indices, drawn per client in cohort order — the exact
        # rng calls the fused path's host staging makes
        idx = np.zeros((c_r, s_max * bs), np.int32)
        for j, c in enumerate(selected):
            steps = int(steps_by_client[c])
            local = clients[c].fixed_batch_indices(bs, steps, rng)
            idx[j, : steps * bs] = parts[c][local]
        server._account_time(dict(info), links_sel)
        plans.append((rnd, selected, weights, ks, ks_overlap, idx))
    return plans


# -------------------------------------------------------------- scan engine
def _run_scan(sim, acfg, rng, clients, parts, fracs_all, links, server,
              steps_by_client, s_max, x_train, y_train, x_test, y_test,
              failure, straggler, collect_overlap,
              per_client_ef: bool = False) -> FLSimResult:
    """Whole-simulation ``lax.scan`` engine: precompute every round's plan on
    host (same rng stream as the fused loop), stack the schedules + batch
    sample indices as scan xs, run ONE jitted program, then evaluate the
    returned per-round model trajectory.

    ``per_client_ef`` switches to the "pop_scan" carry contract: EF
    residuals live in a dense ``[P + 1, n]`` PER-CLIENT matrix (row P is the
    padded-slot sentinel) that every round slot-gathers/scatters by the
    cohort ids — the bit-exact dense reference for the sparse out-of-core
    client store, and the first engine whose EF state survives cohort
    resizes (no ``reset_ef``)."""
    n_sel = cohort_slots(sim.n_clients, sim.participation)
    n_params, v_bytes = server.n_params, server.v_bytes
    bs = sim.batch_size
    ef = acfg.strat.needs_residuals

    plans = _plan_rounds(sim, acfg, rng, clients, parts, fracs_all, links,
                         server, steps_by_client, s_max, failure, straggler,
                         collect_overlap)
    result = FLSimResult()
    if not plans:
        result.times = server.times
        return result

    # ------------------------------------------------- stack xs [R, C, ...]
    r_exec, c_max = len(plans), n_sel
    xs: Dict[str, np.ndarray] = {
        "sample_idx": np.zeros((r_exec, c_max, s_max, bs), np.int32),
        "step_mask": np.zeros((r_exec, c_max, s_max), bool),
        "active": np.zeros((r_exec, c_max), bool),
        "weights": np.zeros((r_exec, c_max), np.float32),
        "ks": np.ones((r_exec, c_max), np.int32),
    }
    if ef and not per_client_ef:
        xs["reset_ef"] = np.zeros((r_exec,), bool)
    if ef and per_client_ef:
        # slot -> client id; padded slots point at the sentinel row P
        xs["cohort"] = np.full((r_exec, c_max), sim.n_clients, np.int32)
    if collect_overlap:
        xs["ks_overlap"] = np.ones((r_exec, c_max), np.int32)
        xs["overlap_round"] = np.zeros((r_exec,), bool)
    # eval-round snapshots land in an O(E x n) carried buffer (the scanned
    # program no longer emits the model every round)
    xs["eval_write"], xs["eval_slot"] = _eval_plan(sim,
                                                   [p[0] for p in plans])
    n_evals = int(xs["eval_write"].sum())
    prev_c = None
    for i, (rnd, selected, weights, ks, ks_overlap, idx) in enumerate(plans):
        c_r = len(selected)
        xs["sample_idx"][i, :c_r] = idx.reshape(c_r, s_max, bs)
        for j, c in enumerate(selected):
            xs["step_mask"][i, j, : int(steps_by_client[c])] = True
        xs["active"][i, :c_r] = True
        xs["weights"][i, :c_r] = weights
        xs["ks"][i, :c_r] = ks
        if ef and per_client_ef:
            xs["cohort"][i, :c_r] = selected
        elif ef:
            # mirrors FLServer.round_fused: residuals reset whenever the
            # cohort size changes between consecutive EXECUTED rounds
            xs["reset_ef"][i] = prev_c is not None and c_r != prev_c
        if ks_overlap is not None:
            xs["ks_overlap"][i, :c_r] = ks_overlap
            xs["overlap_round"][i] = True
        prev_c = c_r

    # --------------------------------------------------- one compiled scan
    x_all, y_all = jnp.asarray(x_train), jnp.asarray(y_train)

    def gather_batches(p):
        idx = p["sample_idx"]
        return {"x": x_all[idx], "y": y_all[idx]}

    sim_fn = engine_mod.make_sim_scan(
        mlp_loss, server.params, lr=sim.lr, acfg=acfg, eta=server.eta,
        with_overlap=collect_overlap, make_batches=gather_batches,
        population=sim.n_clients if per_client_ef else None)
    res_rows = (sim.n_clients + 1) if per_client_ef else c_max
    residuals0 = (jnp.zeros((res_rows, n_params), jnp.float32) if ef
                  else jnp.zeros((0,), jnp.float32))
    evals0 = jnp.zeros((max(n_evals, 1), n_params), jnp.float32)
    xs_dev = {k: jnp.asarray(v) for k, v in xs.items()}
    # AOT-compile so wall_per_round reports the steady-state per-round cost
    # of the compiled trajectory (trace/compile is a one-off, just like the
    # fused engine's warmup rounds that benchmarks discard)
    compiled = sim_fn.compile(server._flat, residuals0, evals0, xs_dev)
    t_exec0 = time.perf_counter()
    out = compiled(server._flat, residuals0, evals0, xs_dev)
    out["flat"].block_until_ready()
    wall = time.perf_counter() - t_exec0

    # --------------------------------------------------------- host post
    server._flat = out["flat"]
    server.params = server._unravel(server._flat)
    evals_out = out["evals"]
    xt, yt = jnp.asarray(x_test), jnp.asarray(y_test)
    for i, (rnd, selected, *_rest) in enumerate(plans):
        if xs["eval_write"][i]:
            snap = evals_out[int(xs["eval_slot"][i])]
            acc = float(mlp_accuracy(server._unravel(snap), xt, yt))
            result.accuracies.append((rnd, acc))
    result.executed_rounds = [p[0] for p in plans]
    result.wall_per_round = [wall / r_exec] * r_exec
    result.times = server.times
    result.final_accuracy = (result.accuracies[-1][1]
                             if result.accuracies else 0.0)
    if ef and per_client_ef:
        # PER-CLIENT matrix [P, n] (sentinel row dropped) — the dense
        # reference the sparse client store is parity-tested against
        result.final_residuals = np.asarray(
            out["residuals"][: sim.n_clients])
    elif ef:
        c_last = len(plans[-1][1])
        server._residuals = out["residuals"][:c_last]
        result.final_residuals = np.asarray(server._residuals)
    if collect_overlap:
        for i, (rnd, selected, *_rest) in enumerate(plans):
            if rnd == sim.rounds // 2:
                result.overlap_hist = _overlap_hist(
                    out["ys"]["overlap_counts"][i], len(selected))
    return result


# -------------------------------------------------------- population engine
def _run_population(sim, acfg, rng, clients, parts, fracs_all, links, server,
                    steps_by_client, s_max, x_train, y_train, x_test, y_test,
                    failure, straggler) -> FLSimResult:
    """Streaming-cohort engine over the sparse out-of-core client store:
    the same host plan as the scan engines (ONE rng stream), but each round
    is a single jitted program whose EF residuals arrive from / return to a
    ``population.ClientStateStore`` in the strategy's declared layout
    (densify-on-gather / sparsify-on-scatter inside the jit). Round state is
    O(C x n) device + O(P x width) host (chunked, spillable) — never
    ``[P, n]`` dense. Bit-exact with ``engine="pop_scan"`` (asserted in
    tests/test_population.py): same plans, same batch gathers, same
    aggregation arithmetic, lossless residual round-trips."""
    from repro.fed import population as pop_mod
    from repro.fed import round_step as rs_mod

    n_sel = cohort_slots(sim.n_clients, sim.participation)
    n_params, v_bytes = server.n_params, server.v_bytes
    bs = sim.batch_size
    strat = acfg.strat
    ef = strat.needs_residuals

    plans = _plan_rounds(sim, acfg, rng, clients, parts, fracs_all, links,
                         server, steps_by_client, s_max, failure, straggler,
                         False)
    result = FLSimResult()
    if not plans:
        result.times = server.times
        return result

    x_all, y_all = jnp.asarray(x_train), jnp.asarray(y_train)

    def gather_batches(x):
        idx = x["sample_idx"]
        return {"x": x_all[idx], "y": y_all[idx]}

    width = 0
    if ef and strat.residual_layout == "topk_complement":
        width = pop_mod.residual_width(
            n_params, min(int(np.min(p[3])) for p in plans))
    step = rs_mod.make_population_round_step(
        mlp_loss, server.params, lr=sim.lr, acfg=acfg, eta=server.eta,
        width=width, make_batches=gather_batches)
    store = None
    if ef:
        store = pop_mod.ClientStateStore(
            sim.n_clients, n_params, layout=strat.residual_layout,
            width=max(width, 1),
            chunk_clients=min(256, sim.n_clients))

    flat = server._flat
    res_dev = step.init_residuals(n_sel, n_params)
    xt, yt = jnp.asarray(x_test), jnp.asarray(y_test)
    for rnd, selected, weights, ks, _ks_overlap, idx in plans:
        t0 = time.perf_counter()
        c_r = len(selected)
        x = {"sample_idx": np.zeros((n_sel, s_max, bs), np.int32),
             "step_mask": np.zeros((n_sel, s_max), bool),
             "active": np.zeros((n_sel,), bool),
             "weights": np.zeros((n_sel,), np.float32),
             "ks": np.ones((n_sel,), np.int32)}
        x["sample_idx"][:c_r] = idx.reshape(c_r, s_max, bs)
        for j, c in enumerate(selected):
            x["step_mask"][j, : int(steps_by_client[c])] = True
        x["active"][:c_r] = True
        x["weights"][:c_r] = weights
        x["ks"][:c_r] = ks
        x = {k: jnp.asarray(v) for k, v in x.items()}
        if ef:
            # pad the gathered cohort rows to the static slot count; the
            # jit's `active` mask round-trips the zero padding untouched
            bufs = []
            for g in store.gather(selected):
                buf = np.zeros((n_sel,) + g.shape[1:], g.dtype)
                buf[:c_r] = g
                bufs.append(jnp.asarray(buf))
            res_dev = (tuple(bufs) if step.layout == "topk_complement"
                       else bufs[0])
        out = step(flat, res_dev, x)
        flat = out["flat"]
        if ef:
            if bool(out["overflow"]):
                raise RuntimeError(
                    f"round {rnd}: EF residual outgrew sparse width "
                    f"{step.width}")
            res_dev = out["residuals"]
            new = (res_dev if isinstance(res_dev, tuple) else (res_dev,))
            store.scatter(selected,
                          tuple(np.asarray(a)[:c_r] for a in new))
        result.wall_per_round.append(time.perf_counter() - t0)
        result.executed_rounds.append(rnd)
        if _is_eval_round(sim, rnd):
            acc = float(mlp_accuracy(server._unravel(flat), xt, yt))
            result.accuracies.append((rnd, acc))

    server._flat = flat
    server.params = server._unravel(flat)
    result.times = server.times
    result.final_accuracy = (result.accuracies[-1][1]
                             if result.accuracies else 0.0)
    if ef:
        # PER-CLIENT [P, n] dense view (parity with pop_scan); small-P
        # engine — the large-P entry point is population.run_population_rounds
        result.final_residuals = store.dump_dense()
    return result


# ----------------------------------------------------- traced-sampling scan
def run_fl_traced(sim: FLSimConfig, acfg: agg_mod.AggregationConfig,
                  p_fail: float = 0.0,
                  straggler: Optional[StragglerPolicy] = None) -> FLSimResult:
    """Fully-traced sampling variant of the scan engine: cohort permutation,
    failure survival draws, straggler arrival deadlines, and batch index
    draws all happen INSIDE the one compiled program from a threaded PRNG
    key (``ft.failures.survivors_traced`` / ``ft.straggler.
    arrival_mask_traced`` masks). Self-consistent stream — the host-rng
    ``engine="scan"`` path remains the seeded parity reference.

    Host-side per-round work is exactly one PRNG key; the BCRS schedule is
    computed once over the full client set (links are round-invariant) and
    gathered per cohort in-jit, with coefficients renormalized over the
    surviving arrivals (``renormalize_coefficients_traced``). The sampled
    cohort is surfaced back to the host per round (ys), so comm-time
    accounting covers exactly the participating clients — the same
    accounting semantics as the host engines.
    """
    (rng, clients, parts, fracs_all,
     (x_train, y_train, x_test, y_test), server) = _setup_sim(sim, acfg)
    links = server.links
    key = jax.random.PRNGKey(sim.seed)
    fracs_all = np.asarray(fracs_all, np.float64)
    n_params, v_bytes = server.n_params, server.v_bytes
    n, bs = sim.n_clients, sim.batch_size

    steps_by_client = _steps_by_client(clients, sim)
    s_max = int(steps_by_client.max())
    n_sel = cohort_slots(n, sim.participation)
    n_draw = min(over_select(n_sel, straggler) if straggler else n_sel, n)

    # round-invariant per-client tables (links don't change, so the BCRS
    # schedule over the FULL client set is computable once on host)
    crs_all, coeffs_all, info = agg_mod.round_schedule(
        acfg, n, fracs_all / fracs_all.sum(), links, v_bytes)
    ks_all = agg_mod.ks_for_schedule(n_params, crs_all)
    cr_eff = acfg.strat.wire.cr_eff(acfg.cr, n_params)
    times_all = np.array([bcrs_mod.comm_time(v_bytes, l, cr_eff)
                          for l in links], np.float32)
    lens = np.array([len(ds) for ds in clients], np.int64)
    table = np.zeros((n, int(lens.max())), np.int32)
    for c, p in enumerate(parts):
        table[c, : len(p)] = p
    smask_all = (np.arange(s_max)[None, :]
                 < steps_by_client[:, None])          # [N, S]

    dev = dict(
        coeffs=jnp.asarray(coeffs_all, jnp.float32),
        ks=jnp.asarray(ks_all, jnp.int32),
        times=jnp.asarray(times_all),
        lens=jnp.asarray(lens, jnp.int32),
        table=jnp.asarray(table),
        smask=jnp.asarray(smask_all),
        x=jnp.asarray(x_train), y=jnp.asarray(y_train))
    weighted_by_coeffs = acfg.strat.weighting == "bcrs"

    def plan_fn(xrow):
        k_perm, k_fail, k_batch = jax.random.split(xrow["key"], 3)
        cohort = jax.random.permutation(k_perm, n)[:n_draw]
        active = survivors_traced(k_fail, n, p_fail)[cohort]
        if straggler is not None:
            t = jnp.where(active, dev["times"][cohort], jnp.inf)
            active = arrival_mask_traced(t, n_sel, straggler)
        coeffs = dev["coeffs"][cohort]
        if weighted_by_coeffs:
            w = renormalize_coefficients_traced(coeffs, active)
        else:
            w = jnp.where(active, coeffs, 0.0)
            w = w / jnp.maximum(jnp.sum(w), 1e-12)
        local = jax.random.randint(
            k_batch, (n_draw, s_max * bs), 0,
            dev["lens"][cohort][:, None])
        idx = jnp.take_along_axis(dev["table"][cohort], local, axis=1)
        return {"sample_idx": idx.reshape(n_draw, s_max, bs),
                "step_mask": dev["smask"][cohort],
                "active": active, "weights": w, "ks": dev["ks"][cohort],
                # surfaced to the host so comm time is accounted over the
                # clients that actually participated, like the host engines
                "ys_extra": {"cohort": cohort, "arrived": active}}

    def gather_batches(p):
        idx = p["sample_idx"]
        return {"x": dev["x"][idx], "y": dev["y"][idx]}

    sim_fn = engine_mod.make_sim_scan(
        mlp_loss, server.params, lr=sim.lr, acfg=acfg, eta=server.eta,
        make_batches=gather_batches, plan_fn=plan_fn)
    ef = acfg.strat.needs_residuals
    residuals0 = (jnp.zeros((n_draw, n_params), jnp.float32) if ef
                  else jnp.zeros((0,), jnp.float32))
    # eval bookkeeping is host-known even under traced sampling: the scanned
    # program snapshots eval rounds into the O(E x n) carried buffer
    eval_write, eval_slot = _eval_plan(sim, range(sim.rounds))
    evals0 = jnp.zeros((max(int(eval_write.sum()), 1), n_params),
                       jnp.float32)
    t0 = time.perf_counter()
    out = sim_fn(server._flat, residuals0, evals0,
                 {"key": jax.random.split(jax.random.fold_in(key, 1),
                                          sim.rounds),
                  "eval_write": jnp.asarray(eval_write),
                  "eval_slot": jnp.asarray(eval_slot)})
    out["flat"].block_until_ready()
    wall = time.perf_counter() - t0

    result = FLSimResult()
    server._flat = out["flat"]
    server.params = server._unravel(server._flat)
    evals_out = out["evals"]
    cohorts = np.asarray(out["ys"]["cohort"])
    arrived = np.asarray(out["ys"]["arrived"])
    xt, yt = jnp.asarray(x_test), jnp.asarray(y_test)
    for rnd in range(sim.rounds):
        # comm time over the clients that actually participated this round
        # (same accounting the host engines do for their cohorts). A round
        # whose whole sampled cohort died contributes nothing — the revived
        # survivor need not be in the cohort — exactly like the host
        # engines' skipped rounds (the in-jit model update is a no-op too).
        sel = cohorts[rnd][arrived[rnd]]
        if sel.size:
            info_r = {"strategy": acfg.strategy}
            if "crs" in info:
                info_r["crs"] = np.asarray(crs_all)[sel]
            server._account_time(info_r, [links[c] for c in sel])
            result.executed_rounds.append(rnd)
        if eval_write[rnd]:
            snap = evals_out[int(eval_slot[rnd])]
            acc = float(mlp_accuracy(server._unravel(snap), xt, yt))
            result.accuracies.append((rnd, acc))
    result.wall_per_round = ([wall / len(result.executed_rounds)]
                             * len(result.executed_rounds)
                             if result.executed_rounds else [])
    result.times = server.times
    result.final_accuracy = (result.accuracies[-1][1]
                             if result.accuracies else 0.0)
    if ef:
        result.final_residuals = np.asarray(out["residuals"])
    return result
