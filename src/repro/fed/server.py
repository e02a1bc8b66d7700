"""FL server: round orchestration around core.aggregation.

Holds the global model (flat vector + unravel), per-client EF residuals,
the time accumulator, and applies  w <- w - eta * agg  per round.

Two execution paths share the same state and host-side BCRS schedule:

  * ``round``        — the legacy eager loop (parity reference): flattens
                       host-side client deltas, compresses/aggregates op by
                       op, updates the flat model on host;
  * ``round_fused``  — ONE jitted program (repro.fed.round_step): local
                       training, compression, EF, OPWA, and the server
                       update run inside a single XLA executable with the
                       flat model / residual buffers donated.

A third engine bypasses the per-round server entirely:
``repro.fed.engine.make_sim_scan`` lowers the whole multi-round simulation
into a single ``lax.scan`` (the simulation harness still threads this
server's flat/residual state and time accumulator through it).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg_mod
from repro.core import bcrs as bcrs_mod
from repro.core import cost_model
from repro.core.compression import flatten_tree
from repro.core import strategies as strat_mod


@dataclass
class FLServer:
    params: object                      # global model pytree
    acfg: agg_mod.AggregationConfig
    eta: float = 1.0                    # server learning rate on the update
    links: Optional[List[bcrs_mod.ClientLink]] = None
    times: cost_model.TimeAccumulator = field(
        default_factory=cost_model.TimeAccumulator)
    _residuals: Optional[jax.Array] = None

    def __post_init__(self):
        flat, self._unravel = flatten_tree(self.params)
        self._flat = flat.astype(jnp.float32)
        self.n_params = int(flat.shape[0])
        self.v_bytes = float(self.n_params * 4)   # fp32 update bytes
        self._fused_step = None
        self._fused_step_overlap = None

    # ------------------------------------------------------------------
    def _selected_links(self, selected):
        return ([self.links[i] for i in selected]
                if self.links is not None else None)

    def _account_time(self, info: dict, links) -> None:
        """Paper §5.2 metrics, shared by both round paths. The strategy's
        declared wire format prices the uploads: dense formats take the
        no-index-overhead ``uncompressed_round``; sparse formats map their
        schedule CRs through ``wire.cr_eff`` (identity for the reference
        idx32+f32 pair, honestly smaller for packed formats like qtopk)."""
        if links is None:
            return
        wire = strat_mod.get(self.acfg.strategy).wire
        if wire.dense:
            rt = cost_model.uncompressed_round(links, self.v_bytes)
        else:
            crs = info.get("crs", np.ones(len(links)))
            rt = cost_model.round_times(links, self.v_bytes,
                                        wire.cr_eff(crs, self.n_params))
        self.times.add(rt)
        info["round_time"] = rt

    # ------------------------------------------------------------------
    def round(self, client_deltas: List, data_fracs: np.ndarray,
              selected: np.ndarray) -> dict:
        """Aggregate one round (legacy eager engine: per-client static-CR
        compression loop — the seed behavior, kept as the fused round's
        parity/benchmark reference). client_deltas: list of pytrees
        (w_t - w_i); ``selected``: client indices (for link lookup)."""
        flat_updates = jnp.stack([flatten_tree(d)[0].astype(jnp.float32)
                                  for d in client_deltas])
        links = self._selected_links(selected)
        if self.acfg.strat.needs_residuals:
            if (self._residuals is None
                    or self._residuals.shape[0] != flat_updates.shape[0]):
                self._residuals = jnp.zeros_like(flat_updates)
            agg, info, new_res = agg_mod.aggregate(
                flat_updates, data_fracs, self.acfg, links=links,
                v_bytes=self.v_bytes, residuals=self._residuals,
                use_loop=True)
            self._residuals = new_res
        else:
            agg, info, _ = agg_mod.aggregate(
                flat_updates, data_fracs, self.acfg, links=links,
                v_bytes=self.v_bytes, use_loop=True)
        self._flat = self._flat - self.eta * agg
        self.params = self._unravel(self._flat)
        self._account_time(info, links)
        return info

    # ------------------------------------------------------------------
    def init_fused(self, loss_fn: Callable, lr: float,
                   collect_overlap: bool = False) -> None:
        """Compile-once setup for ``round_fused``: builds the fused round
        program (plus the Fig. 4 overlap-instrumented variant on demand)."""
        from repro.fed import round_step as rs
        self._fused_step = rs.make_round_step(
            loss_fn, self.params, lr=lr, acfg=self.acfg, eta=self.eta)
        if collect_overlap:
            self._fused_step_overlap = rs.make_round_step(
                loss_fn, self.params, lr=lr, acfg=self.acfg, eta=self.eta,
                with_overlap=True)

    def round_fused(self, batches, step_mask, data_fracs: np.ndarray,
                    selected: np.ndarray, want_overlap: bool = False) -> dict:
        """One fused round: batches is a pytree of [C, S, ...] stacked client
        batches, step_mask [C, S] marks real (non-padded) local steps."""
        if self._fused_step is None:
            raise RuntimeError("call init_fused(loss_fn, lr) first")
        k = int(jax.tree.leaves(batches)[0].shape[0])
        links = self._selected_links(selected)
        crs, weights, info = agg_mod.round_schedule(
            self.acfg, k, data_fracs, links, self.v_bytes)
        ks = jnp.asarray(agg_mod.ks_for_schedule(self.n_params, crs))
        if want_overlap:
            if self._fused_step_overlap is None:
                raise RuntimeError(
                    "round_fused(want_overlap=True) needs "
                    "init_fused(..., collect_overlap=True)")
            ks_overlap = jnp.asarray(
                agg_mod.overlap_ks(self.acfg, info, k, self.n_params))
        else:
            ks_overlap = ks    # ignored by the non-instrumented step

        residuals = None
        if self.acfg.strat.needs_residuals:
            if (self._residuals is None
                    or self._residuals.shape[0] != k):
                self._residuals = jnp.zeros((k, self.n_params), jnp.float32)
            residuals = self._residuals

        step = self._fused_step_overlap if want_overlap else self._fused_step
        out = step(self._flat, residuals, batches, step_mask,
                   jnp.asarray(weights, jnp.float32), ks, ks_overlap)
        self._flat = out["flat"]
        if self.acfg.strat.needs_residuals:
            self._residuals = out["residuals"]
        self.params = self._unravel(self._flat)
        info["loss"] = out["loss"]
        if "overlap_counts" in out:
            info["overlap_counts"] = out["overlap_counts"]
        self._account_time(info, links)
        return info
