"""Mesh-parallel FL round: clients vmapped over the ``data``(×``pod``) axes,
model TP-sharded over ``model``, aggregation via sharded reductions (psum in
the compiled HLO). This is the paper's system as a first-class distributed
feature — the dry-run lowers this step for the paper-representative cells.

Body adapter over ``repro.fed.engine``: ``make_round_body`` assembles ONE
round of the real-model trajectory — masked vmapped local SGD, per-leaf
traced-k compression with EF residuals, OPWA/weighted merge, server update —
entirely from the shared substrate (``engine.make_masked_local_trainer`` +
``engine.compress_merge_leaf``; every Top-K selection has
``core.compression.topk_compress_dynamic`` semantics, megakernel-routed per
leaf under ``use_kernel="auto"`` on TPU). The same body serves both
dispatch granularities:

  * ``make_mesh_round_step`` — one jitted program per round (the legacy
    dispatch loop, kept as the scan's bit-parity reference);
  * ``engine.make_mesh_sim_scan`` — the whole multi-round trajectory as one
    ``lax.scan`` with the params/residual pytrees threaded through the
    donated carry (the ``launch.fl_train`` default).

``fl_train --engine async`` deliberately does NOT route through this body:
its wave trainer (``async_engine.make_wave_train_step``) vmaps the same
``engine.make_masked_local_trainer`` over per-member params gathered from
the version ring — a [Wb, n] second params axis this round-synchronous body
has no slot for — and compresses at the buffer merge, not per upload. The
two legs share the trainer's wave-composition contract (see its docstring),
which is what keeps the mesh sync legs and the async leg comparable.

Per-leaf selection (vs the host-loop simulator's whole-model flatten) keeps
every tensor sharded; per-leaf retained counts come from the shared
``k_for_ratio_traced`` rounding rule, so the host scheduler and the traced
body can never drift. See docs/DESIGN.md §7.
"""
from __future__ import annotations

import collections
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import compression as comp
from repro.core import strategies as strat_mod
from repro.fed.engine import (compress_merge_leaf, densify_rows,
                              flatten_client_trees, make_masked_local_trainer,
                              make_unflatten, sparsify_rows)

#: retrace telemetry for the per-round mesh step: (strategy,) -> traces.
#: The scanned driver's counter lives in engine.TRACE_COUNTS under
#: ("mesh_scan", strategy).
TRACE_COUNTS: collections.Counter = collections.Counter()


def make_round_body(loss_fn: Callable, *, lr_local: float = 1e-2,
                    eta: float = 1.0, strategy: str = "bcrs_opwa",
                    gamma: float = 5.0, overlap_d: int = 1,
                    use_kernel="auto") -> Callable:
    """One real-model FL round as a pure traceable function.

    Returns ``body(params, residuals, batches, step_mask, coeffs, crs,
    active) -> (new_params, new_residuals, loss)``:

      params      pytree (leaves keep their dtypes/shardings);
      residuals   per-leaf EF pytree ([C, *leaf] f32) — required iff the
                  registered strategy carries EF, pass None otherwise;
      batches     pytree with leading [C, S, ...] axes (C cohort slots,
                  sharded over the batch mesh axes);
      step_mask   bool [C, S] — padded local steps are exact no-ops;
      coeffs      f32 [C] merge weights (data fracs or BCRS Eq. 6 p'_i),
                  0 at padded slots;
      crs         f32 [C] traced per-client compression ratios (per-leaf
                  retained counts are ``k_for_ratio_traced(leaf_n, crs)``);
      active      optional bool [C] — padded cohort slots contribute nothing
                  to the merge, the OPWA overlap counts, the loss, or the
                  residual update. None means every slot is real.

    The reported loss is the active-masked mean of each client's last real
    local step's pre-update loss (``make_masked_local_trainer`` semantics).

    The round's phases run under ``jax.named_scope``s, ``fl.local_train``,
    ``fl.merge`` and ``fl.server_step``: names in the compiled program's
    ``op_name`` metadata, by which a profile can split the round's device
    time. They change no arithmetic.
    """
    strat = strat_mod.get(strategy)   # config-time error, names listed
    ef = strat.needs_residuals
    compress = strat.compresses
    local_train = make_masked_local_trainer(loss_fn, lr_local)

    def body(params, residuals, batches, step_mask, coeffs, crs, active):
        if ef and residuals is None:
            raise ValueError(f"{strategy} needs per-leaf residuals")
        with jax.named_scope("fl.local_train"):
            deltas, losses = jax.vmap(local_train, in_axes=(None, 0, 0))(
                params, batches, step_mask)
        w = coeffs.astype(jnp.float32)
        if active is not None:
            w = jnp.where(active, w, 0.0)

        def agg_leaf(p, dl, res):
            """Sharding-preserving per-leaf compression: the bisection and
            aggregation operate on the leaf's natural (TP-sharded) layout —
            reshape(c, -1) would merge sharded dims and force XLA to gather
            the whole leaf per device (§Perf iteration 1)."""
            with jax.named_scope("fl.merge"):
                ks = (comp.k_for_ratio_traced(dl.size // dl.shape[0], crs)
                      if compress else None)
                agg, new_res = compress_merge_leaf(
                    dl, w, ks, strat, gamma=gamma, overlap_d=overlap_d,
                    use_kernel=use_kernel, residuals=res, active=active)
            with jax.named_scope("fl.server_step"):
                new_p = (p.astype(jnp.float32) - eta * agg).astype(p.dtype)
            return new_p, new_res

        leaves_p, treedef = jax.tree.flatten(params)
        leaves_d = treedef.flatten_up_to(deltas)
        leaves_r = (treedef.flatten_up_to(residuals) if ef
                    else [None] * len(leaves_p))
        out = [agg_leaf(p, d, r)
               for p, d, r in zip(leaves_p, leaves_d, leaves_r)]
        new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_res = (jax.tree.unflatten(treedef, [o[1] for o in out])
                   if ef else residuals)

        if active is None:
            loss = jnp.mean(losses)
        else:
            n_act = jnp.maximum(jnp.sum(active.astype(jnp.int32)), 1)
            loss = jnp.sum(jnp.where(active, losses, 0.0)) / n_act
        return new_params, new_res, loss

    return body


def make_mesh_round_step(loss_fn: Callable, *, lr_local: float = 1e-2,
                         eta: float = 1.0, strategy: str = "bcrs_opwa",
                         gamma: float = 5.0, overlap_d: int = 1,
                         use_kernel="auto", donate: bool = True) -> Callable:
    """One jitted per-round program over ``make_round_body`` — the legacy
    dispatch granularity (one compile + R dispatches), kept as the scanned
    driver's bit-parity reference and the ``fl_train --engine round`` path.
    Params and residual buffers are donated (``donate=False`` for callers
    that reuse inputs, e.g. parity tests)."""
    body = make_round_body(loss_fn, lr_local=lr_local, eta=eta,
                           strategy=strategy, gamma=gamma,
                           overlap_d=overlap_d, use_kernel=use_kernel)

    def _step(params, residuals, batches, step_mask, coeffs, crs, active):
        TRACE_COUNTS[(strategy,)] += 1   # host side effect: trace time only
        return body(params, residuals, batches, step_mask, coeffs, crs,
                    active)

    return jax.jit(_step, donate_argnums=(0, 1) if donate else ())


def mesh_residual_width(params_template, cr_min: float) -> int:
    """Conservative sparse-pair width for the mesh population step: the
    per-leaf Top-K keeps >= k_for_ratio_traced(leaf_n, cr) survivors per
    leaf, so a client's whole-model residual nnz is at most
    ``sum_l (leaf_n - k_l)`` at the plan's smallest cr. The traced k uses
    f32 arithmetic where the host uses f64, so each leaf's bound is slacked
    by one survivor — a few extra columns, never a silent overflow."""
    import numpy as np
    n_total, k_total = 0, 0
    for leaf in jax.tree.leaves(params_template):
        ln = int(np.prod(leaf.shape, dtype=np.int64))
        n_total += ln
        k_total += max(1, min(ln, int(np.floor(ln * cr_min)) - 1))
    return max(1, n_total - k_total)


def make_population_round_step(loss_fn: Callable, params_template, *,
                               lr_local: float = 1e-2, eta: float = 1.0,
                               strategy: str = "bcrs_opwa",
                               gamma: float = 5.0, overlap_d: int = 1,
                               use_kernel="auto", width: int = 0,
                               donate: bool = True) -> Callable:
    """Per-leaf population round: ``make_round_body`` with EF residuals
    arriving in the client store's persisted wire layout instead of a
    resident per-leaf carry pytree — the mesh twin of
    ``round_step.make_population_round_step``.

    Inside the jit the wire rows are densified to ``[C, n]``, split per
    row into the per-leaf ``[C, *leaf]`` pytree the body compresses in
    natural layout, then the updated residual pytree is re-flattened and
    re-sparsified. One flat store serves any parameter pytree; the
    conversion is O(C x n) compute with no new HBM-resident state (the
    round body already materializes [C, *leaf] deltas of the same size).

    Signature::

        step(params, res_wire, batches, step_mask, coeffs, crs, active)
          -> (new_params, new_res_wire, loss, overflow)

    ``res_wire`` is ``(idx [C, W] i32, val [C, W] f32)`` for
    "topk_complement" strategies (``width`` from ``mesh_residual_width``),
    a dense ``[C, n]`` f32 matrix for "dense"-layout EF strategies, and a
    ``[0]`` placeholder for carry="none" (passed through).
    """
    strat = strat_mod.get(strategy)
    ef = strat.needs_residuals
    layout = strat.residual_layout if ef else None
    if layout == "topk_complement" and width <= 0:
        raise ValueError(f"{strategy}: topk_complement wire layout needs "
                         "width > 0 (use mesh_residual_width)")
    body = make_round_body(loss_fn, lr_local=lr_local, eta=eta,
                           strategy=strategy, gamma=gamma,
                           overlap_d=overlap_d, use_kernel=use_kernel)
    res_template = jax.tree.map(
        lambda l: jnp.zeros(l.shape, jnp.float32), params_template)
    unflatten_row = make_unflatten(res_template)
    import numpy as np
    n_total = int(sum(np.prod(l.shape, dtype=np.int64)
                      for l in jax.tree.leaves(params_template)))

    def _step(params, res_wire, batches, step_mask, coeffs, crs, active):
        TRACE_COUNTS[("population", strategy)] += 1   # trace time only
        if layout == "topk_complement":
            rows = densify_rows(*res_wire, n_total)
        else:
            rows = res_wire
        res_tree = (jax.vmap(unflatten_row)(rows) if ef else None)
        new_params, new_res_tree, loss = body(
            params, res_tree, batches, step_mask, coeffs, crs, active)
        overflow = jnp.asarray(False)
        if layout == "topk_complement":
            idx, val, overflow = sparsify_rows(
                flatten_client_trees(new_res_tree), width)
            new_wire = (idx, val)
        elif ef:
            new_wire = flatten_client_trees(new_res_tree)
        else:
            new_wire = res_wire
        return new_params, new_wire, loss, overflow

    donate_nums = ((0, 1) if ef else (0,)) if donate else ()
    return jax.jit(_step, donate_argnums=donate_nums)


def make_fl_round_step(model, *, lr_local: float = 1e-2, eta: float = 1.0,
                       gamma: float = 5.0, overlap_d: int = 1,
                       compress: bool = True, use_kernel="auto") -> Callable:
    """Returns jittable ``fl_round(params, client_batches, coeffs, crs)`` —
    the original single-round convenience surface (full cohort, full step
    count, no EF), now a thin wrapper over ``make_round_body``.

    client_batches: pytree with leading [C, n_steps, ...] axes (C = cohort,
    sharded over the batch mesh axes). coeffs: [C] BCRS p'_i. crs: [C] f32
    per-client compression ratios (traced — scheduled per round on host).
    """
    body = make_round_body(model.loss_fn, lr_local=lr_local, eta=eta,
                           strategy="bcrs_opwa" if compress else "fedavg",
                           gamma=gamma, overlap_d=overlap_d,
                           use_kernel=use_kernel)

    def fl_round(params, client_batches, coeffs, crs):
        c, s = jax.tree.leaves(client_batches)[0].shape[:2]
        step_mask = jnp.ones((c, s), bool)
        new_params, _, loss = body(params, None, client_batches, step_mask,
                                   coeffs, crs, None)
        return new_params, loss

    return fl_round
