"""Fused FL round: ONE jitted program per round (paper Alg. 1 hot path).

Thin adapter over the shared substrate in ``repro.fed.engine``: the masked
vmapped local trainer, traced-k compression, batched EF, OPWA merge, and the
server update all come from there — this module only assembles them into the
per-round program and owns its retrace telemetry. The whole-simulation
``lax.scan`` lowering lives in ``engine.make_sim_scan``; the legacy eager
loop stays in ``fed.server.FLServer.round``.

  * clients are stacked on a leading axis and the local trainer is vmapped;
    ragged per-client step counts are handled with a step mask (padded steps
    are exact no-ops, so parity with the sequential loop is preserved);
  * compression, EF and the merge run through
    ``engine.compress_merge_leaf`` on the [C, n] update matrix: the
    traced-k bisection Top-K (one trace serves every BCRS schedule), and on
    a TPU the ``threshold_find`` + ``fused_merge`` Pallas pipeline;
  * the server update ``w ← w − η·agg`` happens inside the same jit with the
    flat parameter and residual buffers donated; the stacked client batch
    buffers are re-staged every round by the harness's double-buffered
    prefetch (round r+1 transfers while round r computes).

Per-round *scalars* (BCRS CRs, Eq. 6 coefficients, retained counts) stay
host-scheduled numpy — they enter as traced [K] inputs, never as static args.
"""
from __future__ import annotations

import collections
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg_mod
from repro.core import compression as comp
from repro.core import opwa as opwa_mod
from repro.fed import engine
# re-exported for API stability (previous home of these helpers)
from repro.fed.engine import (flatten_client_trees, make_masked_local_trainer,
                              make_unflatten)

#: module-wide retrace telemetry: (strategy, with_overlap) -> number of times
#: a fused round step was traced. A simulation is O(1)-compile iff this stays
#: constant as rounds/clients grow (asserted in tests/test_round_step.py).
TRACE_COUNTS: collections.Counter = collections.Counter()


# -------------------------------------------------------------- fused round
class FusedRoundStep:
    """Callable wrapper around the jitted round (retrace telemetry lives in
    the module-level TRACE_COUNTS)."""

    def __init__(self, fn, strategy: str, with_overlap: bool):
        self._fn = fn
        self.strategy = strategy
        self.with_overlap = with_overlap

    def __call__(self, flat, residuals, batches, step_mask, weights, ks,
                 ks_overlap):
        return self._fn(flat, residuals, batches, step_mask, weights, ks,
                        ks_overlap)


def make_round_step(loss_fn: Callable, params_template, *, lr: float,
                    acfg: agg_mod.AggregationConfig, eta: float = 1.0,
                    with_overlap: bool = False) -> FusedRoundStep:
    """Build the fused round program.

    Returned step signature (all array args traced)::

        step(flat [n] f32,            # donated: global model, ravel order
             residuals [C, n] | None, # donated for eftopk
             batches,                 # pytree of [C, S, ...] stacked batches
             step_mask [C, S] bool,   # padded-step validity
             weights [C] f32,         # data fracs or BCRS Eq. 6 coefficients
             ks [C] i32,              # retained count per client
             ks_overlap [C] i32)      # global top-k count for the Fig. 4
                                      # overlap counts (overlap variant only)
        -> {"flat", "residuals", "loss"[, "overlap_counts"]}
    """
    spec = engine.spec_for(acfg)
    strategy = spec.strategy
    unflatten = engine.make_unflatten(params_template)
    local_train = engine.make_masked_local_trainer(loss_fn, lr)

    def _step(flat, residuals, batches, step_mask, weights, ks, ks_overlap):
        # host side effect: runs only at trace time
        TRACE_COUNTS[(strategy, with_overlap)] += 1

        params = unflatten(flat)
        deltas, losses = jax.vmap(local_train, in_axes=(None, 0, 0))(
            params, batches, step_mask)
        updates = engine.flatten_client_trees(deltas)   # [C, n] f32
        agg, new_res = engine.compress_merge_leaf(
            updates, weights, ks, spec.strat, gamma=spec.gamma,
            overlap_d=spec.overlap_d, use_kernel=spec.use_kernel,
            residuals=residuals)

        out = {"flat": flat - eta * agg,
               "residuals": new_res,
               "loss": jnp.mean(losses)}
        if with_overlap:
            # Fig. 4 instrumentation: global top-k masks on the RAW deltas
            # (mirrors the legacy host-side recomputation)
            masks_o = comp.topk_compress_batch(updates, ks_overlap).mask
            out["overlap_counts"] = opwa_mod.overlap_counts(masks_o)
        return out

    # batches/step_mask are deliberately NOT donated: none of the outputs
    # match their byte size, so XLA cannot alias them and the donation would
    # only emit "donated buffers were not usable" warnings. Their staging
    # cost is hidden instead by the harness's double-buffered prefetch
    # (simulation.run_fl stages round r+1 while round r computes).
    donate = (0, 1) if spec.needs_residuals else (0,)
    fn = jax.jit(_step, donate_argnums=donate)
    return FusedRoundStep(fn, strategy, with_overlap)


# -------------------------------------------- population slot-gather round
class PopulationRoundStep:
    """Callable wrapper around the jitted population round: the slot-gather
    adapter between a ``population.ClientStateStore`` and the unchanged
    compress/EF/merge substrate. Residual I/O happens in the store's wire
    layout (sparse ``(idx, val)`` pairs for "topk_complement", full rows
    for "dense"), densified/sparsified INSIDE the jit boundary — the host
    never materializes a ``[P, n]`` (or even a second ``[C, n]``) buffer."""

    def __init__(self, fn, spec, layout, width):
        self._fn = fn
        self.spec = spec
        self.strategy = spec.strategy
        self.layout = layout       # None when the strategy carries no EF
        self.width = width         # sparse pair width (topk_complement only)

    def __call__(self, flat, residuals, x):
        return self._fn(flat, residuals, x)

    def init_residuals(self, cohort: int, n: int):
        """Zero residual buffers in this step's wire layout (what a client
        that never participated gathers from the store)."""
        if self.layout is None:
            return jnp.zeros((0,), jnp.float32)
        if self.layout == "topk_complement":
            return (jnp.zeros((cohort, self.width), jnp.int32),
                    jnp.zeros((cohort, self.width), jnp.float32))
        return jnp.zeros((cohort, n), jnp.float32)


def make_population_round_step(loss_fn: Callable, params_template, *,
                               lr: float, acfg: agg_mod.AggregationConfig,
                               eta: float = 1.0, width: int = 0,
                               make_batches: Callable = None
                               ) -> PopulationRoundStep:
    """Build the population (streaming-cohort) round program.

    The round body is the fused step's, but EF residuals arrive in the
    client store's persisted layout and leave the same way — gather input /
    scatter output instead of a resident donated carry:

        step(flat [n] f32,                        # donated
             residuals,                           # donated; layout-typed:
                                                  #  topk_complement:
                                                  #    (idx [C, W] i32,
                                                  #     val [C, W] f32)
                                                  #  dense: [C, n] f32
                                                  #  carry="none": [0] f32
             x: {"step_mask" [C, S] bool,
                 "active"    [C]    bool,         # padded cohort slots
                 "weights"   [C]    f32,          # 0 at inactive slots
                 "ks"        [C]    i32,
                 + whatever ``make_batches`` consumes (default "batches",
                   a pytree of [C, S, ...] stacked client batches)})
        -> {"flat", "residuals" (same layout), "loss", "overflow"}

    ``width`` is the static sparse-pair width for "topk_complement"
    strategies — ``population.residual_width`` derives it from the whole
    plan's minimum retained count (nnz <= n - k_min, ties only shrink it).
    ``overflow`` (bool scalar) is True iff a row's residual outgrew the
    width; callers assert on it rather than silently truncating EF state.
    Inactive slots round-trip their residuals unchanged (same ``active``
    semantics as ``compress_merge_leaf``), so the host can scatter only the
    real cohort prefix back to the store.
    """
    spec = engine.spec_for(acfg)
    strategy = spec.strategy
    strat = spec.strat
    unflatten = engine.make_unflatten(params_template)
    local_train = engine.make_masked_local_trainer(loss_fn, lr)
    get_batches = make_batches or (lambda x: x["batches"])
    ef = spec.needs_residuals
    layout = strat.residual_layout if ef else None
    if layout == "topk_complement" and width <= 0:
        raise ValueError(
            f"{strategy} persists residuals as topk_complement pairs — "
            "make_population_round_step needs width > 0 (n - k_min)")

    def _step(flat, residuals, x):
        # host side effect: runs only at trace time
        TRACE_COUNTS[("population", strategy)] += 1

        params = unflatten(flat)
        deltas, losses = jax.vmap(local_train, in_axes=(None, 0, 0))(
            params, get_batches(x), x["step_mask"])
        updates = engine.flatten_client_trees(deltas)   # [C, n] f32
        active = x["active"]
        n = updates.shape[1]

        if layout == "topk_complement":
            res_rows = engine.densify_rows(*residuals, n)
        else:
            res_rows = residuals if ef else None
        agg, new_rows = engine.compress_merge_leaf(
            updates, x["weights"], x["ks"], strat, gamma=spec.gamma,
            overlap_d=spec.overlap_d, use_kernel=spec.use_kernel,
            residuals=res_rows, active=active)

        n_act = jnp.maximum(jnp.sum(active.astype(jnp.int32)), 1)
        out = {"flat": flat - eta * agg,
               "loss": jnp.sum(jnp.where(active, losses, 0.0)) / n_act,
               "overflow": jnp.asarray(False)}
        if layout == "topk_complement":
            idx, val, overflow = engine.sparsify_rows(new_rows, width)
            out["residuals"] = (idx, val)
            out["overflow"] = overflow
        elif ef:
            out["residuals"] = new_rows
        else:
            out["residuals"] = residuals
        return out

    fn = jax.jit(_step, donate_argnums=(0, 1) if ef else (0,))
    return PopulationRoundStep(fn, spec, layout, width)
