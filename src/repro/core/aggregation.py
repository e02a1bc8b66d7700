"""Server aggregation strategies (paper Alg. 1).

Strategies consume per-client *flat* updates Δw_i = w_t − w_i (K × n), apply
the chosen compression client-side, and produce the aggregated update the
server subtracts:  w_{t+1} = w_t − η · agg.

Strategies are registered capability records (``repro.core.strategies``) —
this module dispatches on ``compresses`` / ``needs_residuals`` /
``weighting`` / ``overlap_weighted`` / ``value_codec`` and never matches
strategy names, so registry-only strategies (e.g. ``qtopk``) run through the
eager path unchanged. ``strategies.names()`` lists what is available.

The host-side schedule (``round_schedule``) is shared by the eager path here
and the fused jitted round (repro.fed.round_step): per-round CRs/coefficients
stay host-scheduled numpy, everything per-parameter is traced.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcrs as bcrs_mod
from repro.core import compression as comp
from repro.core import opwa as opwa_mod
from repro.core import strategies as strat_mod


@dataclass
class AggregationConfig:
    strategy: str = "fedavg"       # any name in core.strategies.names()
    cr: float = 0.1                # default/uniform compression ratio CR*
    alpha: float = 1.0             # server lr inside coefficients (Eq. 6)
    gamma: float = 5.0             # OPWA enlarge rate
    overlap_d: int = 1             # OPWA required degree of overlap
    use_kernel: object = "auto"    # Pallas kernels: True | False | "auto"

    def __post_init__(self):
        strat_mod.get(self.strategy)   # config-time error, names listed

    @property
    def strat(self) -> strat_mod.Strategy:
        """The registered capability record for ``strategy``."""
        return strat_mod.get(self.strategy)


# ------------------------------------------------------------- host schedule
def round_schedule(acfg: AggregationConfig, k: int, data_fracs: np.ndarray,
                   links=None, v_bytes: float = 0.0
                   ) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Host-side per-round schedule: (crs [k], agg weights [k], info).

    Dispatches on registry capabilities: non-compressing strategies get
    all-ones CRs with data-fraction weights (and no "crs" info key, so the
    server's time accounting takes the dense route exactly as before);
    "data"-weighted compressors get the uniform CR*; "bcrs"-weighted ones
    get the bandwidth schedule's CRs and Eq. 6 coefficients.
    """
    strat = acfg.strat
    info: dict = {"strategy": acfg.strategy}
    f = np.asarray(data_fracs, np.float64)
    if not strat.compresses:
        return np.ones((k,)), f, info
    if strat.weighting == "data":
        crs = np.full((k,), acfg.cr)
        info["crs"] = crs
        return crs, f, info
    assert links is not None and v_bytes > 0, "BCRS needs link models"
    sched = bcrs_mod.make_schedule(links, f, v_bytes, acfg.cr, acfg.alpha)
    info["crs"] = sched.crs
    info["coefficients"] = sched.coefficients
    info["t_bench"] = sched.t_bench
    return sched.crs, sched.coefficients, info


def ks_for_schedule(n: int, crs: np.ndarray) -> np.ndarray:
    """Per-client retained counts for the traced compressors. Computed on
    host in f64 so they match the legacy per-client ``k_for_ratio``
    exactly."""
    return np.asarray([comp.k_for_ratio(n, float(c)) for c in crs],
                      np.int32)


def overlap_ks(acfg: AggregationConfig, info: dict, k: int, n: int
               ) -> np.ndarray:
    """Per-client GLOBAL top-k counts for the Fig. 4 overlap instrumentation
    (mirrors the legacy host-side fallback): schedule CRs when the strategy
    has them, else the configured CR* — fedavg's schedule crs are all-ones
    and would make the histogram degenerate. Shared by the fused round
    server and the scan plan builder so the two engines' histograms agree
    structurally."""
    crs_overlap = info.get("crs", np.full(k, acfg.cr))
    return np.asarray([comp.k_for_ratio(n, float(c)) for c in crs_overlap],
                      np.int32)


# ------------------------------------------------------- client compression
def _compress_fn(acfg: AggregationConfig):
    codec = acfg.strat.value_codec
    if codec is None:
        return comp.topk_compress

    def fn(u, cr):
        c = comp.topk_compress(u, cr)
        # the codec contract is batched ([C, ...] leading client axis);
        # single-client callers add/strip it here
        return comp.Compressed(codec(c.values[None], c.mask[None])[0],
                               c.mask)

    return fn


@functools.partial(jax.jit, static_argnames=("codec",))
def _compress_batch(updates, ks, residuals, codec=None):
    fn = comp.topk_compress_batch
    if codec is not None:
        base = fn
        fn = lambda u, k_: (lambda c: comp.Compressed(
            codec(c.values, c.mask), c.mask))(base(u, k_))
    if residuals is None:
        c = fn(updates, ks)
        return c.values, c.mask, None
    c, new_res = comp.ef_compress_batch(residuals, updates, ks,
                                        compress_batch=fn)
    return c.values, c.mask, new_res


def compress_clients(updates: jax.Array, crs: np.ndarray,
                     acfg: AggregationConfig,
                     residuals: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """updates [K, n] -> (values [K, n], masks [K, n], new_residuals).

    One compiled program with *traced* per-client k — any BCRS schedule
    reuses the same executable (the legacy loop re-lowered ``lax.top_k``
    per distinct static CR). A registered ``value_codec`` rides along as a
    static arg (module-level functions hash stably, so the jit cache stays
    warm).
    """
    ks = jnp.asarray(ks_for_schedule(updates.shape[1], crs))
    return _compress_batch(updates, ks, residuals, acfg.strat.value_codec)


def compress_clients_loop(updates: jax.Array, crs: np.ndarray,
                          acfg: AggregationConfig,
                          residuals: Optional[jax.Array] = None
                          ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Legacy per-client loop (static-CR compressors). Kept as the parity
    reference for the vectorized path."""
    fn = _compress_fn(acfg)
    vals, masks, new_res = [], [], []
    for i in range(updates.shape[0]):
        u = updates[i]
        if residuals is not None:
            c, r = comp.ef_compress(residuals[i], u, float(crs[i]),
                                    compress=lambda x, cr: fn(x, cr))
            new_res.append(r)
        else:
            c = fn(u, float(crs[i]))
        vals.append(c.values)
        masks.append(c.mask)
    return (jnp.stack(vals), jnp.stack(masks),
            jnp.stack(new_res) if residuals is not None else None)


# ------------------------------------------------------------- eager rounds
def aggregate(updates: jax.Array, data_fracs: np.ndarray,
              acfg: AggregationConfig,
              links=None, v_bytes: float = 0.0,
              residuals: Optional[jax.Array] = None,
              use_loop: bool = False
              ) -> Tuple[jax.Array, dict, Optional[jax.Array]]:
    """Run one server aggregation. Returns (agg [n], info, new_residuals).

    ``use_loop=True`` compresses via the legacy per-client static-CR loop
    (the seed behavior the fused round is benchmarked against); the default
    is the single-executable traced-k path.
    """
    strat = acfg.strat
    k, n = updates.shape
    crs, weights, info = round_schedule(acfg, k, data_fracs, links, v_bytes)
    coeffs = jnp.asarray(weights, jnp.float32)

    if not strat.compresses:
        agg = jnp.einsum("k,kn->n", coeffs, updates.astype(jnp.float32))
        return agg, info, None

    compress = compress_clients_loop if use_loop else compress_clients
    res = residuals if strat.needs_residuals else None
    vals, masks, new_res = compress(updates, crs, acfg, res)
    if strat.overlap_weighted:
        agg = opwa_mod.opwa_aggregate(vals, masks, coeffs, acfg.gamma,
                                      acfg.overlap_d)
    else:
        agg = jnp.einsum("k,kn->n", coeffs, vals.astype(jnp.float32))
    return agg, info, new_res
