"""One compression/aggregation substrate for every FL round engine.

Every engine (the fused round program ``fed.round_step``, the scanned
simulation, the async buffer merge, the mesh-parallel round
``fed.mesh_round`` and the compressed pod sync ``dist.grad_sync``) is a thin
adapter over the pure functions here:

  * ``ClientUpdateSpec``      — static description of the client-update
                                pipeline (strategy, OPWA constants, kernel
                                choice), derived from an
                                ``AggregationConfig`` via ``spec_for``;
  * ``compress_merge_leaf``   — the one compress-and-merge function:
                                [C, *shape] updates (a flat [C, n] matrix or
                                one leaf in its natural, possibly TP-sharded
                                layout) -> traced-k Top-K, error feedback,
                                the strategy's codec, OPWA or weighted
                                merge; the dense merge for strategies that
                                do not compress;
  * ``make_sim_scan``         — the ENTIRE multi-round simulation lowered
                                into one ``lax.scan`` over rounds (server
                                flat params + EF residuals threaded as
                                carry, host-precomputed per-round schedules
                                as xs). ONE compile per simulation, zero
                                per-round dispatch.

Every Top-K selection in the tree has
``core.compression.topk_compress_dynamic`` semantics — the traced-k
bit-pattern bisection (ties kept). With ``use_kernel`` on, the whole
compress->EF->merge pipeline lowers to two Pallas kernels
(``kernels.threshold_find`` + ``kernels.fused_merge``) that are bit-exact
with the jnp lowering while making ~9 logical HBM passes over the [C, n]
update matrix instead of ~35; the jnp route stays as the parity reference.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp
from repro.core import opwa as opwa_mod
from repro.core import strategies as strat_mod
from repro.models import flags

#: module-wide retrace telemetry for the scanned simulation:
#: ("sim_scan", strategy, with_overlap) -> number of traces. A simulation is
#: O(1)-compile iff this stays at 1 regardless of rounds/clients (asserted in
#: tests/test_sim_scan.py).
TRACE_COUNTS: collections.Counter = collections.Counter()


# ------------------------------------------------------------------- spec
@dataclass(frozen=True)
class ClientUpdateSpec:
    """Static (trace-time) description of the per-client update pipeline:
    the registered strategy, its OPWA constants and the ``use_kernel``
    tri-state, handed unchanged to ``compress_merge_leaf`` (which resolves
    it). All runtime quantities (per-client retained counts ``ks``,
    weights, residuals) stay traced arguments. Everything strategy-shaped
    is read from the capability record (``core.strategies.get``) — this
    module never matches strategy names."""
    strategy: str = "fedavg"
    gamma: float = 5.0
    overlap_d: int = 1
    use_kernel: object = False     # True | False | "auto"

    def __post_init__(self):
        strat_mod.get(self.strategy)   # config-time error, names listed

    @property
    def strat(self) -> strat_mod.Strategy:
        """The registered capability record (dict lookup — trace-time cheap)."""
        return strat_mod.get(self.strategy)

    @property
    def needs_residuals(self) -> bool:
        return self.strat.needs_residuals


def spec_for(acfg) -> ClientUpdateSpec:
    """AggregationConfig -> ClientUpdateSpec."""
    return ClientUpdateSpec(strategy=acfg.strategy, gamma=acfg.gamma,
                            overlap_d=acfg.overlap_d,
                            use_kernel=acfg.use_kernel)


# ------------------------------------------------------------- flat <-> tree
def _leaf_specs(params_template):
    leaves, treedef = jax.tree.flatten(params_template)
    specs = [(l.shape, l.dtype, int(np.prod(l.shape, dtype=np.int64)))
             for l in leaves]
    return treedef, specs, int(sum(s for _, _, s in specs))


def make_unflatten(params_template) -> Callable:
    """[n] flat f32 -> pytree shaped/dtyped like ``params_template`` (same
    leaf order as ``ravel_pytree``, so it round-trips with ``flatten_tree``)."""
    treedef, specs, n = _leaf_specs(params_template)

    def unflatten(flat):
        out, off = [], 0
        for shape, dtype, size in specs:
            out.append(flat[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(treedef, out)

    return unflatten


def flatten_client_trees(deltas) -> jax.Array:
    """pytree with leading [C, ...] leaves -> [C, n] f32, ravel order."""
    leaves = jax.tree.leaves(deltas)
    return jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves],
        axis=1)


# ------------------------------------------------- sparse EF residual codec
def sparsify_rows(rows: jax.Array, width: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """[C, n] f32 -> (idx [C, width] i32, val [C, width] f32, overflow).

    The jit-side half of the population client-state store's
    "topk_complement" residual layout: a pure-Top-K EF residual is nonzero
    only on the coordinates the selection dropped, so nnz <= n - k and a
    static ``width = n - k_min`` buffer holds it losslessly. A stable
    argsort on the zero-flag packs the nonzero coordinates first (ascending
    index order — deterministic), padding entries carry the zero values at
    their own coordinates, so ``densify_rows`` scatter-adds them back as
    exact no-ops. ``overflow`` is True iff some row has nnz > width — the
    host asserts on it rather than silently truncating a residual.

    Returns (idx i32, val f32, overflow bool scalar).
    """
    zero = rows == 0.0
    order = jnp.argsort(zero, axis=1, stable=True)
    idx = order[:, :width].astype(jnp.int32)
    val = jnp.take_along_axis(rows, order[:, :width], axis=1)
    overflow = jnp.any(jnp.sum(~zero, axis=1) > width)
    return idx, val, overflow


def densify_rows(idx: jax.Array, val: jax.Array, n: int) -> jax.Array:
    """(idx [C, W] i32, val [C, W] f32) -> [C, n] f32 — inverse of
    ``sparsify_rows``. Within a row the indices are a slice of a
    permutation (all distinct), so the scatter-add reconstructs each stored
    value exactly; padding entries add 0.0 at their own coordinate."""
    c = idx.shape[0]
    rows = jnp.zeros((c, n), val.dtype)
    return rows.at[jnp.arange(c)[:, None], idx].add(val)


# ----------------------------------------------------------- masked trainer
def make_masked_local_trainer(loss_fn: Callable, lr: float):
    """``local_train(params, batches, step_mask) -> (delta, last_loss)``.

    Same SGD arithmetic as ``fed.client.make_local_trainer`` but scans a
    *fixed* number of padded steps; steps with ``step_mask`` False leave the
    parameters untouched, so clients with fewer real steps match the ragged
    sequential loop bit-for-bit while keeping one static shape for vmap.
    The reported loss is the pre-update loss of the last real step (one
    forward pass per step via value_and_grad — the legacy trainer's
    post-update loss recompute is a third of its step FLOPs and feeds
    nothing downstream; the deltas are unaffected).

    Wave-composition contract (the async engine's batched dispatch leans on
    this): each vmapped lane reads only its own (params, batches, mask)
    slice, so a client's delta is invariant to the WIDTH of the vmap it
    rides in and to which other clients share the batch — training clients
    one-at-a-time, in eager waves of one, or in padded pow2 wave buckets
    produces bit-identical deltas. Anything added here must preserve that
    (no cross-lane reductions, no width-dependent arithmetic).
    """
    vg_fn = jax.value_and_grad(lambda p, b: loss_fn(p, b)[0])

    def sgd_step(carry, xs):
        params, last_loss = carry
        batch, m = xs
        loss, grads = vg_fn(params, batch)
        new = jax.tree.map(
            lambda p, g: (p - lr * g.astype(p.dtype)).astype(p.dtype),
            params, grads)
        new = jax.tree.map(lambda a, b: jnp.where(m, a, b), new, params)
        loss = jnp.where(m, loss, last_loss)
        return (new, loss), None

    def local_train(params, batches, step_mask):
        n_steps = jax.tree.leaves(batches)[0].shape[0]
        (final, loss), _ = jax.lax.scan(
            sgd_step, (params, jnp.float32(0.0)), (batches, step_mask),
            unroll=flags.scan_unroll(n_steps))
        delta = jax.tree.map(lambda a, b: (a - b).astype(a.dtype),
                             params, final)
        return delta, loss

    return local_train


# ------------------------------------------------------ compress and merge
def compress_merge_leaf(updates: jax.Array, coeffs: jax.Array,
                        ks: Optional[jax.Array],
                        strategy: strat_mod.Strategy, *, gamma: float,
                        overlap_d: int, use_kernel="auto",
                        residuals: Optional[jax.Array] = None,
                        active: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Compress + merge the cohort's updates of ONE leaf (pure,
    jit/vmap-safe) — the one compress-and-merge function of the tree.

    updates: [C, *shape] per-client (or per-pod) updates — a flat [C, n]
    matrix or a leaf in its natural layout: the bisection Top-K reduces over
    all non-client axes, so a TP-sharded leaf never gets reshaped or
    gathered on the jnp route (see mesh_round). coeffs [C] (data fracs or
    BCRS Eq. 6 coefficients); ks [C] i32 traced retained counts (unused,
    and may be None, when the strategy does not compress).

    ``strategy`` is the registered capability record: ``compresses``
    selects the dense merge or the Top-K pipeline, ``overlap_weighted`` the
    OPWA mask, ``value_codec`` / ``kernel_codec`` the codec applied to the
    survivors before the merge AND before the residual update (so EF
    absorbs the codec error). ``residuals`` (matching [C, *shape], f32)
    switch on error feedback; a strategy that carries EF must be given
    them. ``active`` (bool [C]) gates padded cohort slots out of the
    merge, the overlap counts and the residual update: padded rows are
    all-zero updates whose tie-at-zero Top-K mask is all-True.

    ``use_kernel`` is the tri-state (True / False / "auto" = TPU only),
    resolved here and nowhere else. The kernel route runs the leaf through
    the traced-k megakernel pipeline (``threshold_find`` + ``fused_merge``,
    with fused_merge's quantize/dequantize stage for a ``kernel_codec``) on
    a [C, leaf_n] view — bit-exact with the jnp route (per-client selection
    is over the whole leaf either way). It serves every strategy whose
    record declares ``megakernel``, which the registry allows only where
    the kernel can take the strategy's codec. NOTE the view merges the
    leaf's non-client axes, so on a TP-sharded leaf XLA inserts a gather
    first; the jnp route stays sharding-preserving and is the default off
    the TPU.

    Returns (agg [*shape] f32, new_residuals | residuals).
    """
    w = coeffs.astype(jnp.float32)
    if not strategy.compresses:
        # dense exchange: one weighted sum, nothing to select or fuse
        x = updates.astype(jnp.float32)
        if active is not None:
            x = x * active.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.tensordot(w, x, axes=(0, 0)), residuals
    if strategy.needs_residuals and residuals is None:
        raise ValueError(f"{strategy.name} needs residuals")
    if active is not None:
        w = jnp.where(active, w, 0.0)
    if strategy.megakernel and comp.resolve_use_kernel(use_kernel):
        from repro.kernels import ops as kops
        c, shape = updates.shape[0], updates.shape[1:]
        u2 = updates.astype(jnp.float32).reshape(c, -1)
        r2 = (residuals.astype(jnp.float32).reshape(c, -1)
              if residuals is not None else None)
        agg2, new_res2 = kops.megakernel_aggregate(
            u2, ks, w, residuals=r2, active=active,
            opwa=strategy.overlap_weighted, gamma=float(gamma),
            d=int(overlap_d), codec=strategy.kernel_codec or "none")
        return (agg2.reshape(shape),
                new_res2.reshape((c,) + shape) if residuals is not None
                else None)
    x = updates.astype(jnp.float32)
    if residuals is not None:
        x = residuals + x
    c_obj = jax.vmap(comp.topk_compress_dynamic)(x, ks)
    vals, mask = c_obj.values, c_obj.mask
    if strategy.value_codec is not None:
        vals = strategy.value_codec(vals, mask)
    new_res = (x - vals) if residuals is not None else None
    if active is not None:
        # gate padded rows out of the merge/counts; their residuals pass
        # through unchanged
        ax = active.reshape((-1,) + (1,) * (updates.ndim - 1))
        vals = vals * ax
        mask = mask & ax
        if new_res is not None:
            new_res = jnp.where(ax, new_res,
                                residuals.astype(jnp.float32))
    if strategy.overlap_weighted:
        agg = opwa_mod.opwa_aggregate(vals, mask, w, gamma, overlap_d)
    else:
        agg = jnp.tensordot(w, vals, axes=(0, 0))
    return agg, new_res


# ---------------------------------------------------------- scanned simulation
class SimScan:
    """Callable wrapper around the jitted whole-simulation scan program."""

    def __init__(self, fn, spec: ClientUpdateSpec, with_overlap: bool):
        self._fn = fn
        self.spec = spec
        self.with_overlap = with_overlap

    def __call__(self, flat, residuals, evals, xs):
        return self._fn(flat, residuals, evals, xs)

    def compile(self, flat, residuals, evals, xs):
        """AOT lower+compile for the given arguments. The returned compiled
        executable lets callers separate the one-off trace/compile cost from
        steady-state execution (``benchmarks.bench_round --sim-scan`` times
        the executable alone)."""
        return self._fn.lower(flat, residuals, evals, xs).compile()


def make_sim_scan(loss_fn: Callable, params_template, *, lr: float,
                  acfg, eta: float = 1.0, with_overlap: bool = False,
                  make_batches: Optional[Callable] = None,
                  plan_fn: Optional[Callable] = None,
                  population: Optional[int] = None) -> SimScan:
    """Lower the ENTIRE multi-round FL simulation into one ``lax.scan``.

    Where ``round_step.make_round_step`` compiles one round and Python
    dispatches it R times, this compiles the R-round trajectory into a single
    program: the server's flat params and EF residuals thread through the
    scan carry, and everything the host scheduler decides per round (cohort
    composition, BCRS CR schedules, failure/straggler survivors) arrives as
    stacked ``[R, ...]`` scan xs. One compile, zero per-round dispatch.

    Returned program signature (flat, residuals, and evals donated)::

        sim(flat [n] f32,
            residuals [C, n] f32 ([0] when the strategy carries no EF),
            evals [E, n] f32 (zeros; E = number of host eval rounds >= 1),
            xs: {
              "step_mask"  [R, C, S] bool,   # padded-step validity
              "active"     [R, C]    bool,   # padded cohort-slot validity
              "weights"    [R, C]    f32,    # 0 at inactive slots
              "ks"         [R, C]    i32,
              "eval_write" [R]       bool,   # snapshot the model this round
              "eval_slot"  [R]       i32,    # evals row it lands in
              "reset_ef"   [R]       bool,   # eftopk only: cohort resized
              + whatever ``make_batches`` consumes (default: "batches", a
                pytree of [R, C, S, ...] stacked client batches; the
                simulation harness passes [R, C, S, B] sample indices and a
                gather closure instead, which is ~250x smaller host->device),
              + with_overlap: "ks_overlap" [R, C] i32, "overlap_round" [R]
            })
        -> {"flat": [n], "residuals": [C, n], "evals": [E, n],
            "ys": {"loss" [R][, "overlap_counts" [R, n]]}}

    ``evals[xs["eval_slot"][r]]`` is the server model AFTER each round r
    with ``eval_write``, so the accuracy trajectory is computed by the exact
    same jitted eval as the per-round engines. The buffer is carried through
    the scan and indexed by eval slot — O(E x n) device memory instead of
    the O(rounds x n) a per-round ``ys["flat"]`` stack would cost (asserted
    in tests/test_sim_scan.py). Eval bookkeeping is read from the RAW xs
    row, never from ``plan_fn``'s output, so traced-sampling plans need not
    thread it through.

    Rounds skipped by failure injection (empty cohort) should simply not be
    included in the xs — the carry is untouched by construction, which
    matches the per-round engines' ``continue``.

    ``plan_fn`` (optional) maps each raw xs slice to the per-round plan dict
    consumed above — the hook that lets cohort sampling, survival draws, and
    straggler arrivals run fully *inside* the jit from a threaded PRNG key
    (``simulation.run_fl_traced``) instead of arriving host-precomputed.
    When a traced plan omits "reset_ef", EF residuals are never reset (the
    traced stream has its own slot semantics).

    ``population=P`` switches the carry contract to PER-CLIENT residual
    semantics (the "pop_scan" engine — the dense reference for the sparse
    out-of-core client store): ``residuals`` becomes a ``[P + 1, n]``
    per-client matrix, the xs gain ``"cohort" [R, C] i32`` (slot -> client
    id), and every round gathers the sampled clients' rows into the static
    ``[C, n]`` slots, runs the unchanged round body, and scatters the
    updated rows back. Row P is a sentinel: padded cohort slots point at it
    and scatter back exactly what they gathered (zeros), so duplicate
    sentinel writes are value-identical and the row provably stays zero.
    ``reset_ef`` is ignored — per-client residuals survive cohort resizes
    by construction, which is the point. Only meaningful for small P (the
    dense carry is O(P x n)); the O(P x (n - k_min)) production path is
    ``round_step.make_population_round_step`` + ``population.ClientStateStore``.
    """
    spec = spec_for(acfg)
    unflatten = make_unflatten(params_template)
    local_train = make_masked_local_trainer(loss_fn, lr)
    get_batches = make_batches or (lambda x: x["batches"])
    ef = spec.needs_residuals
    per_client = population is not None

    def body(carry, x):
        flat, res, evals = carry
        p = plan_fn(x) if plan_fn is not None else x
        params = unflatten(flat)
        deltas, losses = jax.vmap(local_train, in_axes=(None, 0, 0))(
            params, get_batches(p), p["step_mask"])
        updates = flatten_client_trees(deltas)     # [C, n] f32
        active = p["active"]

        if ef and per_client:
            res_in = res[x["cohort"]]              # [C, n] slot gather
        else:
            res_in = res
            if ef and "reset_ef" in p:
                res_in = jnp.where(p["reset_ef"], jnp.zeros_like(res), res)
        agg, new_res = compress_merge_leaf(
            updates, p["weights"], p["ks"], spec.strat, gamma=spec.gamma,
            overlap_d=spec.overlap_d, use_kernel=spec.use_kernel,
            residuals=res_in if ef else None, active=active)
        if ef and per_client:
            # scatter updated rows back to the per-client store; padded
            # slots rewrite the sentinel row with what they read (zeros),
            # so duplicate sentinel writes stay deterministic
            rows = jnp.where(active[:, None], new_res, res_in)
            new_res = res.at[x["cohort"]].set(rows)
        new_flat = flat - eta * agg

        # eval-round snapshot: O(E x n) carried buffer instead of emitting
        # the model every round (eval fields come from the raw xs row)
        evals = jax.lax.cond(
            x["eval_write"],
            lambda ev: ev.at[x["eval_slot"]].set(new_flat),
            lambda ev: ev, evals)

        n_act = jnp.maximum(jnp.sum(active.astype(jnp.int32)), 1)
        loss = jnp.sum(jnp.where(active, losses, 0.0)) / n_act
        ys = {"loss": loss}
        # a traced plan_fn can surface per-round plan facts (e.g. the in-jit
        # sampled cohort) to the host via "ys_extra"
        if "ys_extra" in p:
            ys.update(p["ys_extra"])
        if with_overlap:
            # Fig. 4 instrumentation: global top-k masks on the RAW deltas,
            # computed only on the flagged round (cond skips the work
            # everywhere else)
            def counts_fn(args):
                u, ko, act = args
                m = comp.topk_compress_batch(u, ko).mask & act[:, None]
                return opwa_mod.overlap_counts(m)

            ys["overlap_counts"] = jax.lax.cond(
                p["overlap_round"], counts_fn,
                lambda args: jnp.zeros((updates.shape[1],), jnp.int32),
                (updates, p["ks_overlap"], active))
        return (new_flat, new_res if ef else res, evals), ys

    scan_kind = "pop_scan" if per_client else "sim_scan"

    def _sim(flat, residuals, evals, xs):
        # host side effect: runs only at trace time
        TRACE_COUNTS[(scan_kind, spec.strategy, with_overlap)] += 1
        (flat, residuals, evals), ys = jax.lax.scan(
            body, (flat, residuals, evals), xs)
        return {"flat": flat, "residuals": residuals, "evals": evals,
                "ys": ys}

    fn = jax.jit(_sim, donate_argnums=(0, 1, 2))
    return SimScan(fn, spec, with_overlap)


# ------------------------------------------------------- scanned mesh driver
class MeshSimScan:
    """Callable wrapper around the jitted multi-round mesh program (one
    ``lax.scan`` chunk of the real-model FL trajectory)."""

    def __init__(self, fn, strategy: str, ef: bool):
        self._fn = fn
        self.strategy = strategy
        self.ef = ef

    def __call__(self, params, residuals, xs):
        return self._fn(params, residuals, xs)

    def compile(self, params, residuals, xs):
        """AOT lower+compile for the given chunk shapes. The jit cache keys
        on shapes, so chunks of equal length reuse ONE executable; callers
        (``launch.fl_train``) use this to separate the per-chunk-shape
        compile from steady-state dispatch."""
        return self._fn.lower(params, residuals, xs).compile()


def init_mesh_residuals(params_template, cohort: int):
    """Per-leaf EF residual pytree for the mesh engines: one f32
    ``[cohort, *leaf]`` buffer per parameter leaf (the per-leaf twin of the
    flat-space ``[C, n]`` residual matrix the simulation engines carry)."""
    return jax.tree.map(
        lambda l: jnp.zeros((cohort,) + tuple(l.shape), jnp.float32),
        params_template)


def make_mesh_sim_scan(loss_fn: Callable, params_template, *, lr: float,
                       strategy: str = "bcrs_opwa", eta: float = 1.0,
                       gamma: float = 5.0, overlap_d: int = 1,
                       use_kernel="auto") -> MeshSimScan:
    """Lower a multi-round REAL-MODEL FL trajectory into one ``lax.scan``.

    The pytree-native twin of ``make_sim_scan``: where the simulation scan
    carries a flat ``[n]`` vector, this carries the (possibly TP/FSDP-
    sharded) params pytree itself plus a per-leaf EF residual pytree
    (``[C, *leaf]`` per leaf, eftopk only) — every round body operates on
    leaves in their natural layout through ``mesh_round.make_round_body`` /
    ``compress_merge_leaf``, so sharded tensors stay sharded across the
    whole compiled program and the carry buffers are donated in place.

    Returned program signature (params and residuals donated)::

        run(params,                      # pytree, any leaf dtypes/shardings
            residuals,                   # per-leaf [C, *leaf] f32 pytree
                                         # (zeros-[0] placeholder when the
                                         # strategy carries no EF)
            xs: {"batches"   pytree of [T, C, S, ...] stacked client batches,
                 "step_mask" [T, C, S] bool,   # padded-step validity
                 "active"    [T, C]    bool,   # padded cohort-slot validity
                 "weights"   [T, C]    f32,    # 0 at inactive slots
                 "crs"       [T, C]    f32})   # per-client BCRS ratios
        -> {"params", "residuals", "ys": {"loss" [T]}}

    ``T`` is a CHUNK of rounds, not necessarily the whole run: the driver
    scans checkpoint_every-round chunks so every checkpoint boundary is a
    host round-trip (params + residuals come back, get persisted, and are
    fed — donated — into the next chunk). Chunks of equal length hit the
    same jit cache entry, so a run compiles once per distinct chunk length
    (tracked in TRACE_COUNTS[("mesh_scan", strategy)]).

    Per-leaf retained counts are derived in-body from the per-client ``crs``
    via ``core.compression.k_for_ratio_traced`` — the same rounding rule the
    host scheduler uses, applied to each leaf's element count.
    """
    from repro.fed.mesh_round import make_round_body  # cycle-free at runtime
    body_fn = make_round_body(loss_fn, lr_local=lr, eta=eta,
                              strategy=strategy, gamma=gamma,
                              overlap_d=overlap_d, use_kernel=use_kernel)
    ef = strat_mod.get(strategy).needs_residuals

    def scan_body(carry, x):
        params, res = carry
        new_params, new_res, loss = body_fn(
            params, res if ef else None, x["batches"], x["step_mask"],
            x["weights"], x["crs"], x["active"])
        return (new_params, new_res if ef else res), {"loss": loss}

    def _run(params, residuals, xs):
        # host side effect: runs only at trace time
        TRACE_COUNTS[("mesh_scan", strategy)] += 1
        (params, residuals), ys = jax.lax.scan(
            scan_body, (params, residuals), xs)
        return {"params": params, "residuals": residuals, "ys": ys}

    fn = jax.jit(_run, donate_argnums=(0, 1))
    return MeshSimScan(fn, strategy, ef)
