"""FedBuff-style asynchronous buffered-aggregation engine (``engine="async"``).

Clients train against whatever server version is current when they are
dispatched; their updates stream back through a fault-tolerant arrival
process (``ft.arrivals``: mid-transfer failures, resume-from-offset retries,
exponential backoff, per-upload deadlines) into a K-slot buffer. When the
buffer fills — or stalls past a configurable deadline and flushes partially —
the server merges it in ONE compiled program: the same
``engine.compress_merge_leaf`` substrate every synchronous engine uses, fed
staleness-discounted coefficients (``w_i / (1 + s_i)^alpha``,
``core.bcrs.staleness_discount``) so updates computed against old versions
count less.

Batched dispatch (docs/DESIGN.md §12): instead of paying one jit dispatch of
the train program per upload, dispatches are recorded as PENDING and
materialized lazily in *waves* — one vmapped/padded program call covering
every buffer member at flush time (plus forced retirements at version-ring
evictions and checkpoint saves). Each wave member trains against the server
version it was dispatched at, gathered by version id from a small ring of
retained parameter versions inside the jit. Waves are padded to power-of-two
shape buckets, so the program compiles once per bucket — a small bounded set
— and the masked trainer's padded rows are exact no-ops, keeping the batched
path bit-exact with per-client dispatch (``async_batch_dispatch=False``).
A bonus of laziness: uploads that abort are never trained at all.

Per-client EF residuals live either in a dense ``[P + 1, n]`` host store
(``async_dense_store`` — sentinel row P, the pop_scan convention; the
small-P reference) or, by default, in PR 7's sparse out-of-core
``population.ClientStateStore``: rows persist in the strategy's declared
``residual_layout`` ("topk_complement" ``(idx32, f32)`` pairs or chunked
dense rows), densified/sparsified INSIDE the merge jit, gathered/scattered
only for the flushed buffer members — so ``engine="async"`` scales to the
population sizes the sync engines reached in PR 7 with no P-sized aval in
any compiled program.

Crash safety: every piece of loop state — params, the residual store, buffer
contents, in-flight uploads (including their updates and retry timelines),
and the dispatch/selection counters — checkpoints through
``repro.checkpoint`` at flush boundaries (pending dispatches are
materialized first, so the checkpoint tree layout is mode-independent; the
sparse store snapshots chunk-wise next to the main file). All randomness is
counter-based (``np.random.default_rng((seed, tag, counter))``), so
restoring the counters reproduces the exact future: a crash-restarted run
is bit-identical to an uninterrupted one.

Degenerate configuration = synchronous parity anchor: with arrivals forced
synchronous (``async_sync_arrivals``), buffer size = cohort size, and zero
staleness (by construction), the engine replays the scan engine's host plans
through the same two compiled programs and reproduces its trajectory
(pop_scan's, for per-client-EF strategies).
"""
from __future__ import annotations

import collections
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcrs as bcrs_mod
from repro.core import cost_model
from repro.fed import engine as engine_mod
from repro.ft.arrivals import ArrivalProcess, BATCH_TAG
from repro.ft.straggler import renormalize_coefficients

#: trace counters keyed ("async_train" | "async_merge", strategy) — tests
#: assert the buffer-merge program compiles exactly once per run and the
#: train program once per wave shape bucket (a small bounded set)
TRACE_COUNTS: collections.Counter = collections.Counter()

#: rng-stream tag for free-client selection draws (pinned; keyed on the
#: dispatch counter, so selection needs no extra checkpoint state)
SELECT_TAG = 27_449


def wave_bucket(w: int) -> int:
    """Pad-to-bucket width for a wave of ``w`` members: the next power of
    two. Buckets bound the compile count of the wave train program at
    ``log2(max(K, M)) + 1`` regardless of how wave sizes vary."""
    return 1 << max(0, int(w - 1).bit_length())


def min_version_ring(concurrency: int, buffer_k: int) -> int:
    """Config-time floor on the version-ring depth — the *observable
    staleness bound* a ring must clear to batch at all. With ``M <= K``
    every in-flight upload CAN land in the very next flush, so retaining
    the current version suffices (depth 1). With ``M > K`` the pigeonhole
    guarantees uploads from the previous version are still in flight after
    any flush, so a 1-deep ring would force-retire every wave down to
    near-per-client dispatch — require depth 2. Deeper staleness than the
    ring retains is handled gracefully at runtime (forced retirement
    trains a pending wave before its version is evicted — batching
    degrades, correctness never does)."""
    return 1 if concurrency <= buffer_k else 2


# ----------------------------------------------------- compiled programs
class AsyncTrainStep:
    """Jitted local-training program: flat params + a batch plan for C slots
    -> stacked flat client deltas [C, n]. Same arithmetic as the scanned
    engines' in-loop training (vmapped masked SGD over gathered batches).
    Used by the sync-arrivals parity anchor; the event loop trains through
    ``WaveTrainStep`` (same arithmetic, per-member version gather)."""

    def __init__(self, fn, strategy: str):
        self._fn = fn
        self.strategy = strategy

    def __call__(self, flat, x):
        return self._fn(flat, x)


def make_async_train_step(loss_fn: Callable, params_template, *, lr: float,
                          make_batches: Callable,
                          strategy: str = "") -> AsyncTrainStep:
    unflatten = engine_mod.make_unflatten(params_template)
    local_train = engine_mod.make_masked_local_trainer(loss_fn, lr)

    def _train(flat, x):
        TRACE_COUNTS[("async_train", strategy)] += 1
        params = unflatten(flat)
        deltas, _losses = jax.vmap(local_train, in_axes=(None, 0, 0))(
            params, make_batches(x), x["step_mask"])
        return engine_mod.flatten_client_trees(deltas)

    return AsyncTrainStep(jax.jit(_train), strategy)


class WaveTrainStep:
    """Jitted wave-training program: a ring of retained parameter versions
    [V, n] + a padded wave plan -> stacked flat deltas [Wb, n]. Each wave
    member gathers ITS dispatch-time server version by ring slot
    (``x["ver_idx"]``) inside the jit, so one program call replaces Wb
    per-client dispatches while every member still trains against exactly
    the params it would have seen eagerly. Compiles once per wave shape
    bucket (TRACE_COUNTS key ("async_train", strategy) counts traces)."""

    def __init__(self, fn, strategy: str):
        self._fn = fn
        self.strategy = strategy

    def __call__(self, ring, x):
        return self._fn(ring, x)


def make_wave_train_step(loss_fn: Callable, params_template, *, lr: float,
                         make_batches: Callable,
                         strategy: str = "") -> WaveTrainStep:
    unflatten = engine_mod.make_unflatten(params_template)
    local_train = engine_mod.make_masked_local_trainer(loss_fn, lr)

    def _train(ring, x):
        TRACE_COUNTS[("async_train", strategy)] += 1
        flat_w = ring[x["ver_idx"]]                      # [Wb, n]
        params = jax.vmap(unflatten)(flat_w)
        deltas, _losses = jax.vmap(local_train, in_axes=(0, 0, 0))(
            params, make_batches(x), x["step_mask"])
        return engine_mod.flatten_client_trees(deltas)

    return WaveTrainStep(jax.jit(_train), strategy)


class AsyncMergeStep:
    """Jitted buffer-merge program (the ONE compiled merge per run): K
    buffered flat updates + staleness-discounted weights + per-slot EF
    residuals -> new flat params + new residuals. ``layout`` names the
    residual wire format crossing the jit boundary: "rows" (dense [K, n],
    the in-RAM reference), "topk_complement" (sparse ``(idx, val)`` pairs
    densified on entry / sparsified on exit — the population store's
    format), or None (carry="none")."""

    def __init__(self, fn, spec, layout: Optional[str], width: int):
        self._fn = fn
        self.spec = spec
        self.layout = layout
        self.width = width

    def __call__(self, flat, residuals, x):
        return self._fn(flat, residuals, x)


def make_async_merge_step(acfg, *, eta: float = 1.0,
                          residual_layout: str = "rows",
                          width: int = 0) -> AsyncMergeStep:
    spec = engine_mod.spec_for(acfg)
    ef = spec.needs_residuals
    layout = residual_layout if ef else None
    if layout == "topk_complement" and width <= 0:
        raise ValueError(
            f"{spec.strategy} persists residuals as topk_complement pairs — "
            "make_async_merge_step needs width > 0 (n - k_min)")

    def _merge(flat, residuals, x):
        TRACE_COUNTS[("async_merge", spec.strategy)] += 1
        if layout == "topk_complement":
            res_rows = engine_mod.densify_rows(*residuals, flat.shape[0])
        else:
            res_rows = residuals if ef else None
        agg, new_rows = engine_mod.compress_merge_leaf(
            x["updates"], x["weights"], x["ks"], spec.strat,
            gamma=spec.gamma, overlap_d=spec.overlap_d,
            use_kernel=spec.use_kernel, residuals=res_rows,
            active=x["active"])
        out = {"flat": flat - eta * agg,
               "overflow": jnp.asarray(False)}
        if layout == "topk_complement":
            idx, val, overflow = engine_mod.sparsify_rows(new_rows, width)
            out["residuals"] = (idx, val)
            out["overflow"] = overflow
        else:
            out["residuals"] = new_rows if ef else residuals
        return out

    fn = jax.jit(_merge, donate_argnums=(0, 1) if ef else (0,))
    return AsyncMergeStep(fn, spec, layout, width)


# -------------------------------------------------------- flush weighting
def flush_weights(member_ids, member_staleness, pending_ids,
                  pending_staleness, *, buffer_k: int, alpha: float,
                  coeff_table: Optional[np.ndarray] = None,
                  fracs_all: Optional[np.ndarray] = None) -> np.ndarray:
    """Final merge coefficients for the ``m`` filled buffer slots.

    Every slot — filled or not — gets the staleness-discounted coefficient
    of its (actual or expected) occupant: filled slots their buffered
    client, unfilled slots the next in-flight uploads the buffer was
    waiting for when it stalled. ``renormalize_coefficients`` then folds
    the missing slots' mass onto the arrived ones, so a partial flush takes
    the same total step magnitude the full buffer would have (the invariant
    tests/test_async_engine.py asserts). A full flush renormalizes to
    itself — the discounted coefficients pass through untouched.

    ``coeff_table`` (whole-population Eq. 6 coefficients) serves
    bcrs-weighted strategies, the ``run_fl_traced`` convention; otherwise
    data fractions are normalized over the slots' occupants."""
    ids = np.concatenate([np.asarray(member_ids, np.int64),
                          np.asarray(pending_ids, np.int64)])[:buffer_k]
    stal = np.concatenate([np.asarray(member_staleness, np.float64),
                           np.asarray(pending_staleness, np.float64)
                           ])[:buffer_k]
    if coeff_table is not None:
        base = np.asarray(coeff_table, np.float64)[ids]
    else:
        fr = np.asarray(fracs_all, np.float64)[ids]
        base = fr / fr.sum()
    disc = bcrs_mod.staleness_discount(base, stal, alpha)
    coeffs_k = np.zeros((buffer_k,), np.float64)
    coeffs_k[: len(ids)] = disc
    arrived = np.zeros((buffer_k,), bool)
    m = len(np.asarray(member_ids))
    arrived[:m] = True
    return renormalize_coefficients(coeffs_k, arrived)[:m]


# ------------------------------------------------------- event-driven loop
class BufferedAsyncLoop:
    """The FedBuff event loop, generic over the model: drivers supply
    ``batch_plan(client, uid) -> {name: np row}`` (one client's local-batch
    plan, NO leading axis; all batch randomness MUST key on
    ``(seed, BATCH_TAG, uid)`` so restarts replay it), a ``wave_train``
    program consuming stacked plan rows, and ``on_flush(flush_idx, flat,
    rt)`` (eval/accounting). The loop owns dispatch, the arrival process,
    the buffer, staleness weighting, the EF residual store, and crash-safe
    checkpointing.

    Virtual time: ``dispatch`` resolves each upload's full retry timeline
    immediately; events pop in time order; a flush happens when the buffer
    fills or — if a stall deadline is set — when the deadline passes with
    the buffer partially full. In-flight concurrency is topped up to M
    after every event; a client is busy from dispatch until its upload
    aborts or its buffered update is flushed, so no client ever has two
    updates in the pipeline (which is what keeps per-client EF exact).

    Training is LAZY by default (``batch_dispatch``): a dispatch records a
    pending entry; pending members materialize in one padded wave program
    call when the buffer flushes, when their parameter version is about to
    leave the retention ring (forced retirement), or when a checkpoint
    saves. Because the masked vmapped trainer is width- and
    padding-invariant, the wave path is bit-exact with eager per-client
    dispatch (``batch_dispatch=False`` trains each dispatch as a wave of
    one — the sequential baseline the dispatch-count benchmark compares
    against).

    ``residual_store``: None -> dense ``[P + 1, n]`` host array for
    carry="ef" strategies (sentinel row P); a
    ``population.ClientStateStore`` -> sparse out-of-core rows in the
    store's layout, which must match ``merge.layout``. Host round state is
    then O(K·n + M·n + V·n + resident-chunks) — never O(P·n)."""

    def __init__(self, *, n_clients: int, n_params: int, buffer_k: int,
                 concurrency: int, target_flushes: int, seed: int,
                 alpha: float, stall_s: float,
                 p_fail: float, retry: cost_model.RetryPolicy,
                 links, v_bytes: float, cr_eff_all: np.ndarray,
                 ks_all: np.ndarray, coeff_table: Optional[np.ndarray],
                 fracs_all: np.ndarray, merge: AsyncMergeStep,
                 wave_train: WaveTrainStep,
                 batch_plan: Callable[[int, int], Dict[str, np.ndarray]],
                 on_flush: Callable, batch_dispatch: bool = True,
                 version_ring: int = 8,
                 residual_store=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 extra_state: Optional[Callable[[], dict]] = None,
                 load_extra: Optional[Callable[[dict], None]] = None):
        if buffer_k > n_clients:
            raise ValueError(f"async buffer K={buffer_k} exceeds the "
                             f"client population {n_clients}")
        need = min_version_ring(concurrency, buffer_k)
        if version_ring < need:
            raise ValueError(
                f"async version ring depth {version_ring} is below the "
                f"observable staleness bound {need} for M={concurrency} "
                f"in-flight over a K={buffer_k} buffer")
        self.n, self.n_params = n_clients, n_params
        self.k, self.m_conc = buffer_k, concurrency
        self.target = target_flushes
        self.seed, self.alpha, self.stall_s = seed, alpha, stall_s
        self.links, self.v_bytes = links, v_bytes
        self.cr_eff_all = np.asarray(cr_eff_all, np.float64)
        self.ks_all = np.asarray(ks_all, np.int32)
        self.coeff_table = coeff_table
        self.fracs_all = np.asarray(fracs_all, np.float64)
        self.merge = merge
        self.ef = merge.spec.needs_residuals
        self.wave_train, self.batch_plan = wave_train, batch_plan
        self.batch_dispatch = batch_dispatch
        self.on_flush = on_flush
        self.ckpt_dir, self.ckpt_every = checkpoint_dir, checkpoint_every
        self.extra_state = extra_state or (lambda: {})
        self.load_extra = load_extra or (lambda d: None)

        if self.ef and residual_store is None:
            residual_store = np.zeros((n_clients + 1, n_params), np.float32)
        self.store = residual_store if self.ef else None
        self.dense_store = isinstance(self.store, np.ndarray)
        if self.ef and not self.dense_store:
            # store layout "dense" crosses the jit boundary as "rows"
            want = ("topk_complement"
                    if self.store.layout == "topk_complement" else "rows")
            if merge.layout != want:
                raise ValueError(
                    f"merge program speaks residual layout {merge.layout!r} "
                    f"but the client store persists {self.store.layout!r}")
        elif self.ef and merge.layout != "rows":
            raise ValueError(
                f"merge program speaks residual layout {merge.layout!r} but "
                "the dense [P + 1, n] store only carries \"rows\" — pass a "
                "population.ClientStateStore as residual_store")

        self.proc = ArrivalProcess(seed=seed, p_fail=p_fail, retry=retry)
        self.flat: Optional[jax.Array] = None
        self.buffer: List[dict] = []
        #: uid -> (client, version): dispatched but not yet trained
        self.pending: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        #: uid -> np [n]: trained updates awaiting flush (or abort)
        self.inflight_updates: Dict[int, np.ndarray] = {}
        #: clients with an update in the pipeline — O(M + K) entries, a
        #: set (not a [P] bool column) so membership state stays O(C)
        self.busy: set = set()
        self.version = 0
        self.flushes = 0
        self.now = 0.0
        self.t_prev_flush = 0.0
        self.stall_t = float("inf")
        # ---- version retention ring (host mirror + lazy device copy) ----
        self.ring_depth = version_ring
        self.ring = np.zeros((version_ring, n_params), np.float32)
        self.ring_ver = np.full((version_ring,), -1, np.int64)
        self._ring_dev = None
        # ---- telemetry the dispatch benchmark reads ---------------------
        self.train_calls = 0          # jit dispatches of the train program
        self.train_rows = 0           # client updates computed (incl. waves)
        self.wave_sizes: List[int] = []
        self.wave_buckets_used: set = set()
        self.forced_retires = 0       # waves forced by ring eviction
        self.aborted_untrained = 0    # aborted uploads never trained (lazy)
        self.peak_round_state_bytes = 0

    # ------------------------------------------------------------ dispatch
    def _dispatch(self, client: int) -> None:
        ev = self.proc.dispatch(client, self.version, self.now,
                                self.links[client], self.v_bytes,
                                float(self.cr_eff_all[client]))
        self.pending[ev.uid] = (client, self.version)
        self.busy.add(client)
        if not self.batch_dispatch:
            self._materialize([ev.uid])

    def _top_up(self) -> None:
        while len(self.proc) < self.m_conc:
            if len(self.busy) >= self.n:
                return
            # O(1)-expected free-client draw: rejection-sample the busy set
            # (|busy| <= M + K << P at population scale) instead of an O(P)
            # flatnonzero scan. Keyed on the dispatch counter, so the draw
            # sequence — including rejections — replays exactly on restore.
            rng = np.random.default_rng(
                (self.seed, SELECT_TAG, self.proc.counter))
            while True:
                client = int(rng.integers(self.n))
                if client not in self.busy:
                    break
            self._dispatch(client)

    # ------------------------------------------------- wave materialization
    def _materialize(self, uids) -> None:
        """Train the pending entries in ``uids`` as ONE padded wave program
        call (uid order — deterministic, and irrelevant to the bits: each
        member's batches key on its own uid and its params come from its
        own dispatch version's ring slot)."""
        uids = sorted(u for u in uids if u in self.pending)
        if not uids:
            return
        members = [(u, *self.pending.pop(u)) for u in uids]
        w = len(members)
        wb = wave_bucket(w)
        plans = [self.batch_plan(c, u) for u, c, _v in members]
        x: Dict[str, jax.Array] = {}
        for key, row0 in plans[0].items():
            row0 = np.asarray(row0)
            buf = np.zeros((wb,) + row0.shape, row0.dtype)
            for j, p in enumerate(plans):
                buf[j] = p[key]
            x[key] = jnp.asarray(buf)
        ver_idx = np.zeros((wb,), np.int32)
        for j, (_u, _c, v) in enumerate(members):
            slot = v % self.ring_depth
            if self.ring_ver[slot] != v:      # pragma: no cover — guarded
                raise RuntimeError(
                    f"version {v} left the retention ring before its wave "
                    "materialized (forced retirement should prevent this)")
            ver_idx[j] = slot
        x["ver_idx"] = jnp.asarray(ver_idx)
        if self._ring_dev is None:
            self._ring_dev = jnp.asarray(self.ring)
        out = np.asarray(self.wave_train(self._ring_dev, x))
        for j, (u, _c, _v) in enumerate(members):
            self.inflight_updates[u] = out[j]
        self.train_calls += 1
        self.train_rows += w
        self.wave_sizes.append(w)
        self.wave_buckets_used.add(wb)
        self._note_state()

    def _advance_version(self) -> None:
        """Retire the new server version into the ring. If the slot being
        overwritten still holds a version some pending dispatch trained
        against, that wave materializes NOW (forced retirement) — batching
        degrades gracefully instead of losing the params."""
        self.version += 1
        slot = self.version % self.ring_depth
        evicted = int(self.ring_ver[slot])
        if evicted >= 0:
            stale = [u for u, (_c, v) in self.pending.items()
                     if v == evicted]
            if stale:
                self.forced_retires += 1
                self._materialize(stale)
        self.ring[slot] = np.asarray(self.flat)
        self.ring_ver[slot] = self.version
        self._ring_dev = None

    def _note_state(self) -> None:
        """Peak host round-state telemetry: ring + trained updates + store
        residency (+ the [K, n] flush staging buffer, counted at flush).
        Registry-style O(P) planning columns (links, per-client CRs/ks) are
        setup state, not round state — the PR 7 accounting convention."""
        b = self.ring.nbytes
        b += sum(u.nbytes for u in self.inflight_updates.values())
        if self.ef:
            b += (self.store.nbytes if self.dense_store
                  else self.store.resident_bytes())
        self.peak_round_state_bytes = max(self.peak_round_state_bytes, b)

    # --------------------------------------------------------------- flush
    def _flush(self, t_flush: float) -> None:
        m = len(self.buffer)
        self._materialize([b["uid"] for b in self.buffer])
        ids = np.array([b["client"] for b in self.buffer], np.int64)
        stal = self.version - np.array([b["version"] for b in self.buffer],
                                       np.int64)
        pend = self.proc.in_flight()[: self.k - m]
        w = flush_weights(
            ids, stal, [e.client for e in pend],
            [self.version - e.version for e in pend],
            buffer_k=self.k, alpha=self.alpha,
            coeff_table=self.coeff_table, fracs_all=self.fracs_all)
        updates = np.zeros((self.k, self.n_params), np.float32)
        wpad = np.zeros((self.k,), np.float32)
        kpad = np.ones((self.k,), np.int32)
        act = np.zeros((self.k,), bool)
        for j, b in enumerate(self.buffer):
            updates[j] = self.inflight_updates.pop(b["uid"])
        wpad[:m], kpad[:m], act[:m] = w, self.ks_all[ids], True
        out = self.merge(self.flat, self._gather_residuals(ids),
                         {"updates": jnp.asarray(updates),
                          "weights": jnp.asarray(wpad),
                          "ks": jnp.asarray(kpad),
                          "active": jnp.asarray(act)})
        self.flat = out["flat"]
        if self.ef:
            if (self.merge.layout == "topk_complement"
                    and bool(out["overflow"])):
                raise RuntimeError(
                    f"flush {self.flushes}: EF residual outgrew the sparse "
                    f"width {self.merge.width} — the schedule emitted a k "
                    "below the width's k_min")
            self._scatter_residuals(ids, out["residuals"], m)
        dur = [b["t_arrive"] - b["t_dispatch"] for b in self.buffer]
        rt = cost_model.RoundTime(actual=t_flush - self.t_prev_flush,
                                  max=float(np.max(dur)),
                                  min=float(np.min(dur)))
        self.busy.difference_update(int(c) for c in ids)
        self.buffer.clear()
        self.t_prev_flush = t_flush
        self.stall_t = float("inf")
        self.peak_round_state_bytes = max(
            self.peak_round_state_bytes,
            self.ring.nbytes + updates.nbytes)
        self.on_flush(self.flushes, self.flat, rt)
        self._advance_version()
        self.flushes += 1
        self._note_state()

    def _gather_residuals(self, ids: np.ndarray):
        """Buffer members' residuals, padded to the K static slots, in the
        merge program's layout. Dense mode gathers by sentinel-padded row
        ids (row P is never written, so padded slots read exact zeros);
        store mode gathers only the real members and zero-pads — the same
        values, since a never-flushed client's store rows are zeros."""
        if not self.ef:
            return jnp.zeros((0,), jnp.float32)
        if self.dense_store:
            ids_pad = np.full((self.k,), self.n, np.int64)
            ids_pad[: len(ids)] = ids
            return jnp.asarray(self.store[ids_pad])
        rows = self.store.gather(ids)
        padded = []
        for a in rows:
            buf = np.zeros((self.k,) + a.shape[1:], a.dtype)
            buf[: len(ids)] = a
            padded.append(jnp.asarray(buf))
        return (tuple(padded) if self.merge.layout == "topk_complement"
                else padded[0])

    def _scatter_residuals(self, ids: np.ndarray, res_out, m: int) -> None:
        if self.dense_store:
            self.store[ids] = np.asarray(res_out)[:m]
        else:
            arrays = res_out if isinstance(res_out, tuple) else (res_out,)
            self.store.scatter(ids, tuple(np.asarray(a)[:m]
                                          for a in arrays))

    # ------------------------------------------------------- checkpointing
    # Large f32 tensors ride in the checkpoint TREE; every scalar /
    # timestamp / counter rides in msgpack ``extra`` — msgpack floats are
    # exact float64 round-trips, whereas restored tree leaves come back as
    # jnp arrays (float64 would be squashed to f32 under the default x64
    # setting, silently perturbing the replayed event timeline).
    _EV_COLS = ("uid", "client", "version", "t_dispatch", "t_resolve",
                "arrived", "attempts", "progress", "timed_out")

    def _ckpt_like(self) -> dict:
        return {
            "flat": jnp.zeros((self.n_params,), jnp.float32),
            "residuals": (np.zeros_like(self.store) if self.dense_store
                          else np.zeros((0,), np.float32)),
            "buf_updates": np.zeros((self.k, self.n_params), np.float32),
            "if_updates": np.zeros((self.m_conc, self.n_params),
                                   np.float32),
        }

    def _save(self) -> None:
        from repro import checkpoint as ckpt_mod
        from repro.fed import population as pop_mod
        # materialize every pending dispatch so the in-flight update tensor
        # is complete — the checkpoint layout is dispatch-mode-independent
        # (and bit-safe: training is wave-composition-invariant)
        self._materialize(list(self.pending))
        tree = self._ckpt_like()
        tree["flat"] = self.flat
        if self.ef and self.dense_store:
            tree["residuals"] = self.store
        st = self.proc.state()
        uids = [int(u) for u in st["uid"]]
        for j, b in enumerate(self.buffer):
            tree["buf_updates"][j] = self.inflight_updates[int(b["uid"])]
        for j, uid in enumerate(uids):
            tree["if_updates"][j] = self.inflight_updates[uid]
        extra = {
            "counter": self.proc.counter, "version": self.version,
            "flushes": self.flushes, "now": self.now,
            "t_prev_flush": self.t_prev_flush,
            "stall_t": None if np.isinf(self.stall_t) else self.stall_t,
            "buffer": [[int(b["client"]), int(b["version"]), int(b["uid"]),
                        float(b["t_arrive"]), float(b["t_dispatch"])]
                       for b in self.buffer],
            "inflight": {col: [c.item() for c in st[col]]
                         for col in self._EV_COLS},
        }
        if self.ef and not self.dense_store:
            extra["client_store"] = self.store.save(self.ckpt_dir,
                                                    self.flushes)
        extra.update(self.extra_state())
        ckpt_mod.save(self.ckpt_dir, self.flushes, tree, extra=extra)
        if self.ef and not self.dense_store:
            # retention just ran on the step files; drop the client-store
            # snapshots whose step it pruned
            pop_mod.prune_client_snapshots(
                self.ckpt_dir, ckpt_mod.list_steps(self.ckpt_dir))

    def _restore(self) -> bool:
        from repro import checkpoint as ckpt_mod
        from repro.fed import population as pop_mod
        if not self.ckpt_dir or not ckpt_mod.list_steps(self.ckpt_dir):
            return False
        tree, step, extra = ckpt_mod.restore_latest_valid(
            self.ckpt_dir, self._ckpt_like())
        self.flat = tree["flat"]
        if self.ef and self.dense_store:
            # np.array (copy): asarray of a jnp leaf is a read-only view,
            # and the store is scattered into on every flush
            self.store = np.array(tree["residuals"], np.float32)
        elif self.ef:
            man = extra["client_store"]
            if (man["layout"], man["width"]) != (self.store.layout,
                                                 self.store.width):
                raise ValueError(
                    f"client-store snapshot persists layout "
                    f"{man['layout']!r} width {man['width']} but this run "
                    f"expects {self.store.layout!r}/{self.store.width} — "
                    "the strategy or schedule changed across the restart")
            self.store = pop_mod.ClientStateStore.restore(
                self.ckpt_dir, step, man,
                max_resident_chunks=self.store.max_resident_chunks,
                spill_dir=self.store.spill_dir)
        self.buffer = [
            {"client": c, "version": v, "uid": u, "t_arrive": ta,
             "t_dispatch": td}
            for c, v, u, ta, td in extra["buffer"]]
        inflight = extra["inflight"]
        dtypes = {"uid": np.int64, "client": np.int64, "version": np.int64,
                  "t_dispatch": np.float64, "t_resolve": np.float64,
                  "arrived": bool, "attempts": np.int64,
                  "progress": np.float64, "timed_out": bool}
        state = {col: np.asarray(inflight[col], dtypes[col])
                 for col in self._EV_COLS}
        state["counter"] = np.array([extra["counter"]], np.int64)
        self.proc.load_state(state)
        self.pending.clear()
        self.inflight_updates = {
            int(uid): np.asarray(tree["if_updates"][j])
            for j, uid in enumerate(inflight["uid"])}
        for j, b in enumerate(self.buffer):
            self.inflight_updates[int(b["uid"])] = \
                np.asarray(tree["buf_updates"][j])
        self.version, self.flushes = extra["version"], extra["flushes"]
        self.now = extra["now"]
        self.t_prev_flush = extra["t_prev_flush"]
        self.stall_t = (float("inf") if extra["stall_t"] is None
                        else extra["stall_t"])
        self.busy = {b["client"] for b in self.buffer}
        self.busy |= self.proc.busy_clients()
        # pending is empty after a restore (the save materialized it), so
        # retaining only the current version reproduces the exact future
        self.ring[:] = 0.0
        self.ring_ver[:] = -1
        slot = self.version % self.ring_depth
        self.ring[slot] = np.asarray(self.flat)
        self.ring_ver[slot] = self.version
        self._ring_dev = None
        self.load_extra(extra)
        return True

    # ----------------------------------------------------------- main loop
    def run(self, flat0, stop_after: Optional[int] = None) -> jax.Array:
        """Drive the loop to ``target_flushes`` (or ``stop_after``, to
        simulate a crash at a flush boundary). Resumes from the newest
        intact checkpoint when one exists. Returns the final flat params."""
        self.flat = flat0
        if not self._restore():
            self.ring[0] = np.asarray(self.flat)
            self.ring_ver[0] = self.version
            self._ring_dev = None
        # top-up is idempotent at full concurrency; after a restore it
        # replays the dispatches the original run made right after the
        # checkpointed flush (counter-keyed draws -> identical events)
        self._top_up()
        # no-progress guard: a config whose uploads can NEVER arrive (e.g.
        # a timeout below every link's latency) would otherwise redispatch
        # aborts forever; at any positive arrival probability the chance of
        # this many consecutive aborts is astronomically small
        aborts_in_a_row, abort_limit = 0, 1000 * max(self.m_conc, 8)
        while self.flushes < self.target:
            if stop_after is not None and self.flushes >= stop_after:
                return self.flat
            t_next = self.proc.peek_time()
            if self.buffer and (t_next is None or self.stall_t < t_next):
                # stall deadline passed (or nothing else can ever arrive):
                # flush partially with renormalized coefficients
                t = self.now if t_next is None and np.isinf(self.stall_t) \
                    else self.stall_t
                self.now = max(self.now, t)
                self._flush(self.now)
                self._after_flush()
                self._top_up()
                continue
            if t_next is None:
                break        # nothing in flight, nothing buffered
            ev = self.proc.pop()
            self.now = ev.t_resolve
            if ev.arrived:
                aborts_in_a_row = 0
                self.buffer.append({
                    "client": ev.client, "version": ev.version,
                    "uid": ev.uid, "t_arrive": ev.t_resolve,
                    "t_dispatch": ev.t_dispatch})
                if len(self.buffer) == 1:
                    self.stall_t = self.now + self.stall_s
                if len(self.buffer) >= self.k:
                    self._flush(self.now)
                    self._after_flush()
            else:
                # upload aborted (retries exhausted or deadline hit): if
                # still pending it was NEVER trained — lazy dispatch saves
                # the work outright; EF untouched either way (residuals
                # only change on merge)
                if ev.uid in self.pending:
                    self.pending.pop(ev.uid)
                    self.aborted_untrained += 1
                else:
                    self.inflight_updates.pop(ev.uid)
                self.busy.discard(ev.client)
                aborts_in_a_row += 1
                if aborts_in_a_row > abort_limit:
                    raise RuntimeError(
                        f"{abort_limit} consecutive upload aborts without "
                        "one arrival — the failure/timeout config admits "
                        "no progress (is async_upload_timeout_s below the "
                        "links' latencies?)")
            self._top_up()
        return self.flat

    def _after_flush(self) -> None:
        if (self.ckpt_dir and self.ckpt_every
                and self.flushes % self.ckpt_every == 0):
            self._save()


# ------------------------------------------------------ simulation driver
def validate_async_config(sim, n_clients: Optional[int] = None) -> None:
    """Config-time validation of the ``async_*`` knobs (run_fl and the mesh
    driver both call this BEFORE any loop state exists): the buffer must
    fit the population, and the version ring must clear the observable
    staleness bound (``min_version_ring``) for the effective concurrency."""
    from repro.fed import simulation as sim_mod
    n = sim.n_clients if n_clients is None else n_clients
    n_sel = sim_mod.cohort_slots(n, sim.participation)
    k_buf = sim.async_buffer_k or n_sel
    if k_buf > n:
        raise ValueError(f"async buffer K={k_buf} exceeds the client "
                         f"population {n}")
    m_conc = sim.async_concurrency or max(1, min(2 * k_buf, n - k_buf))
    need = min_version_ring(m_conc, k_buf)
    if sim.async_version_ring < need:
        raise ValueError(
            f"async_version_ring={sim.async_version_ring} is below the "
            f"observable staleness bound {need} for M={m_conc} in-flight "
            f"over a K={k_buf} buffer — deepen the ring (depth 2 suffices "
            "for any M > K; forced retirement covers deeper staleness)")
    if sim.async_store_resident and not sim.async_store_spill:
        raise ValueError("async_store_resident bounds the sparse store's "
                         "resident chunks — set async_store_spill to the "
                         "directory evicted chunks spill into")


def run_async_sim(sim, acfg, rng, clients, parts, fracs_all, links, server,
                  steps_by_client, s_max, x_train, y_train, x_test, y_test,
                  failure, straggler, checkpoint_dir: Optional[str] = None,
                  checkpoint_every: int = 0,
                  stop_after: Optional[int] = None):
    """``run_fl(engine="async")`` body. Two modes:

    * ``sim.async_sync_arrivals``: the parity anchor — replays the shared
      host round plans (``_plan_rounds``, same rng stream as every sync
      engine) through the async train + merge programs with zero staleness.
      Reproduces the scan engine's trajectory (pop_scan's for EF
      strategies, whose per-client residual convention this engine shares).
    * general: the event-driven FedBuff loop with the fault-tolerant
      arrival process; ``sim.rounds`` counts buffer flushes. ``failure`` /
      ``straggler`` are subsumed by the arrival process here (slow links
      arrive late, uploads fail/retry/abort per ``async_p_fail_upload``).
      EF residuals default to the sparse ``ClientStateStore``
      (``sim.async_dense_store`` opts back into the dense ``[P + 1, n]``
      reference); dispatches batch into waves unless
      ``sim.async_batch_dispatch`` is off.
    """
    from repro.core import aggregation as agg_mod
    from repro.core.compression import k_for_ratio
    from repro.fed import population as pop_mod
    from repro.fed import simulation as sim_mod

    validate_async_config(sim)
    result = sim_mod.FLSimResult()
    n, n_params, v_bytes = sim.n_clients, server.n_params, server.v_bytes
    strat, ef, bs = acfg.strat, acfg.strat.needs_residuals, sim.batch_size
    n_sel = sim_mod.cohort_slots(n, sim.participation)
    x_all, y_all = jnp.asarray(x_train), jnp.asarray(y_train)
    xt, yt = jnp.asarray(x_test), jnp.asarray(y_test)

    def gather_batches(x):
        idx = x["sample_idx"]
        return {"x": x_all[idx], "y": y_all[idx]}

    if sim.async_sync_arrivals:
        train = make_async_train_step(
            sim_mod.mlp_loss, server.params, lr=sim.lr,
            make_batches=gather_batches, strategy=acfg.strategy)
        merge = make_async_merge_step(acfg, eta=server.eta)
        return _run_sync_parity(sim, acfg, rng, clients, parts, fracs_all,
                                links, server, steps_by_client, s_max,
                                failure, straggler, train, merge, xt, yt,
                                result)

    # -------------------------------------------------- general async mode
    k_buf = sim.async_buffer_k or n_sel
    m_conc = sim.async_concurrency or max(1, min(2 * k_buf, n - k_buf))
    fracs_norm = np.asarray(fracs_all, np.float64)
    fracs_norm = fracs_norm / fracs_norm.sum()
    crs_all, coeffs_all, _info = agg_mod.round_schedule(
        acfg, n, fracs_norm, links, v_bytes)
    ks_all = agg_mod.ks_for_schedule(n_params, crs_all)
    # dense wire formats return a scalar 1.0 — broadcast to per-client
    cr_eff_all = np.broadcast_to(np.asarray(
        strat.wire.cr_eff(np.asarray(crs_all, np.float64), n_params),
        np.float64), (n,))
    retry = cost_model.RetryPolicy(
        max_attempts=sim.async_max_attempts, backoff_s=sim.async_backoff_s,
        backoff_factor=sim.async_backoff_factor,
        timeout_s=sim.async_upload_timeout_s)

    store = None
    if ef and not sim.async_dense_store:
        layout = strat.residual_layout
        width = (pop_mod.residual_width(n_params, int(ks_all.min()))
                 if layout == "topk_complement" else 0)
        store = pop_mod.ClientStateStore(
            n, n_params, layout=layout, width=width,
            chunk_clients=min(sim.async_store_chunk, n),
            max_resident_chunks=sim.async_store_resident or None,
            spill_dir=sim.async_store_spill or None)
        merge = make_async_merge_step(
            acfg, eta=server.eta,
            residual_layout=("topk_complement"
                             if layout == "topk_complement" else "rows"),
            width=width)
    else:
        merge = make_async_merge_step(acfg, eta=server.eta)

    wave_train = make_wave_train_step(
        sim_mod.mlp_loss, server.params, lr=sim.lr,
        make_batches=gather_batches, strategy=acfg.strategy)

    def batch_plan(client: int, uid: int) -> Dict[str, np.ndarray]:
        rng_b = np.random.default_rng((sim.seed, BATCH_TAG, uid))
        steps = int(steps_by_client[client])
        local = clients[client].fixed_batch_indices(bs, steps, rng_b)
        idx = np.zeros((s_max, bs), np.int32)
        idx[:steps] = parts[client][local].reshape(steps, bs)
        smask = np.zeros((s_max,), bool)
        smask[:steps] = True
        return {"sample_idx": idx, "step_mask": smask}

    def on_flush(flush_idx: int, flat, rt: cost_model.RoundTime) -> None:
        server.times.add(rt)
        result.executed_rounds.append(flush_idx)
        if sim_mod._is_eval_round(sim, flush_idx):
            acc = float(sim_mod.mlp_accuracy(server._unravel(flat), xt, yt))
            result.accuracies.append((flush_idx, acc))

    def extra_state() -> dict:
        return {"accuracies": [[int(r), float(a)]
                               for r, a in result.accuracies],
                "executed_rounds": [int(r) for r in result.executed_rounds],
                "times": [[float(t.actual), float(t.max), float(t.min)]
                          for t in server.times.per_round]}

    def load_extra(extra: dict) -> None:
        result.accuracies = [(int(r), float(a))
                             for r, a in extra["accuracies"]]
        result.executed_rounds = list(extra["executed_rounds"])
        for a, mx, mn in extra["times"]:
            server.times.add(cost_model.RoundTime(a, mx, mn))

    loop = BufferedAsyncLoop(
        n_clients=n, n_params=n_params, buffer_k=k_buf, concurrency=m_conc,
        target_flushes=sim.rounds, seed=sim.seed, alpha=sim.async_alpha,
        stall_s=sim.async_stall_s, p_fail=sim.async_p_fail_upload,
        retry=retry, links=links, v_bytes=v_bytes, cr_eff_all=cr_eff_all,
        ks_all=ks_all,
        coeff_table=(coeffs_all if strat.weighting == "bcrs" else None),
        fracs_all=fracs_all, merge=merge, wave_train=wave_train,
        batch_plan=batch_plan, on_flush=on_flush,
        batch_dispatch=sim.async_batch_dispatch,
        version_ring=sim.async_version_ring, residual_store=store,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        extra_state=extra_state, load_extra=load_extra)
    t0 = time.perf_counter()
    flat = loop.run(server._flat, stop_after=stop_after)
    wall = time.perf_counter() - t0

    server._flat = flat
    server.params = server._unravel(flat)
    result.times = server.times
    result.final_accuracy = (result.accuracies[-1][1]
                             if result.accuracies else 0.0)
    nf = max(len(result.executed_rounds), 1)
    result.wall_per_round = [wall / nf] * len(result.executed_rounds)
    if ef:
        result.final_residuals = (np.asarray(loop.store[:n])
                                  if loop.dense_store
                                  else loop.store.dump_dense())
    result.async_loop = loop
    return result


def _run_sync_parity(sim, acfg, rng, clients, parts, fracs_all, links,
                     server, steps_by_client, s_max, failure, straggler,
                     train, merge, xt, yt, result):
    """Degenerate-async parity mode: synchronous arrivals, buffer = cohort,
    staleness 0 (discount is the exact identity at s=0 for any alpha)."""
    from repro.fed import simulation as sim_mod
    n, n_params, bs = sim.n_clients, server.n_params, sim.batch_size
    n_sel = sim_mod.cohort_slots(n, sim.participation)
    ef = acfg.strat.needs_residuals

    plans = sim_mod._plan_rounds(sim, acfg, rng, clients, parts, fracs_all,
                                 links, server, steps_by_client, s_max,
                                 failure, straggler, False)
    if not plans:
        result.times = server.times
        return result
    store = (np.zeros((n + 1, n_params), np.float32) if ef
             else np.zeros((0,), np.float32))
    flat = server._flat
    for rnd, selected, weights, ks, _ko, idx in plans:
        t0 = time.perf_counter()
        c_r = len(selected)
        x = {"sample_idx": np.zeros((n_sel, s_max, bs), np.int32),
             "step_mask": np.zeros((n_sel, s_max), bool)}
        x["sample_idx"][:c_r] = idx.reshape(c_r, s_max, bs)
        for j, c in enumerate(selected):
            x["step_mask"][j, : int(steps_by_client[c])] = True
        updates = train(flat, {k: jnp.asarray(v) for k, v in x.items()})
        ids_pad = np.full((n_sel,), n, np.int64)
        ids_pad[:c_r] = selected
        wpad = np.zeros((n_sel,), np.float32)
        wpad[:c_r] = weights
        kpad = np.ones((n_sel,), np.int32)
        kpad[:c_r] = ks
        act = np.zeros((n_sel,), bool)
        act[:c_r] = True
        res_rows = (jnp.asarray(store[ids_pad]) if ef
                    else jnp.zeros((0,), jnp.float32))
        out = merge(flat, res_rows, {"updates": updates,
                                     "weights": jnp.asarray(wpad),
                                     "ks": jnp.asarray(kpad),
                                     "active": jnp.asarray(act)})
        flat = out["flat"]
        if ef:
            store[selected] = np.asarray(out["residuals"])[:c_r]
        result.wall_per_round.append(time.perf_counter() - t0)
        result.executed_rounds.append(rnd)
        if sim_mod._is_eval_round(sim, rnd):
            acc = float(sim_mod.mlp_accuracy(server._unravel(flat), xt, yt))
            result.accuracies.append((rnd, acc))

    server._flat = flat
    server.params = server._unravel(flat)
    result.times = server.times
    result.final_accuracy = (result.accuracies[-1][1]
                             if result.accuracies else 0.0)
    if ef:
        result.final_residuals = np.asarray(store[:n])
    return result
