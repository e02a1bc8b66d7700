"""Update/gradient compressors: exact Top-K (static and traced k), Rand-K,
stochastic quantization, and error-feedback wrappers.

All compressors operate on flat f32/bf16 vectors; ``flatten_tree`` /
``unflatten_tree`` move between pytrees and vectors. The dense-masked
representation (values kept, others zero + bool mask) is bit-exact with the
paper's simulation; ``to_sparse``/``from_sparse`` give the (indices, values)
wire format whose byte count the cost model and the compressed pod-sync use.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


class Compressed(NamedTuple):
    values: jax.Array   # dense masked vector [n]
    mask: jax.Array     # bool [n]


# ---------------------------------------------------------------- tree utils
def flatten_tree(tree) -> Tuple[jax.Array, Callable]:
    flat, unravel = ravel_pytree(tree)
    return flat, unravel


def k_for_ratio(n: int, cr: float) -> int:
    """Host-side retained count for compression ratio ``cr`` over ``n``
    parameters: round(n·cr) clamped to [1, n] (CR=1 keeps everything
    exactly). The ONE place the rounding rule lives — the traced twin below
    must mirror any change, and every scheduler/engine routes through one of
    the two (duplicating the clip/round inline is a silent-drift hazard)."""
    return max(1, min(n, int(round(n * cr))))


def k_for_ratio_traced(n: int, crs: jax.Array) -> jax.Array:
    """Traced twin of ``k_for_ratio`` for in-jit per-client/per-pod CRs:
    crs (any shape, traced f32) -> i32 retained counts, same
    clip(round(cr·n), 1, n) rule. ``n`` stays static (it is a leaf size).

    The host variant rounds in f64, this one in f32 — for the CR grids the
    schedulers emit the two agree exactly (asserted in tests); keep ratios
    away from .5/n boundaries if bit-parity with host scheduling matters.
    """
    return jnp.clip(jnp.round(crs.astype(jnp.float32) * n).astype(jnp.int32),
                    1, n)


def resolve_use_kernel(flag) -> bool:
    """``use_kernel`` tri-state: True / False / "auto" (Pallas on TPU,
    XLA reference elsewhere — same detection as dist.grad_sync)."""
    if flag == "auto":
        return jax.devices()[0].platform == "tpu"
    return bool(flag)


# ------------------------------------------------------------------- top-k
def topk_compress(u: jax.Array, cr: float) -> Compressed:
    """Exact global magnitude Top-K. u: flat [n]."""
    n = u.shape[0]
    k = k_for_ratio(n, cr)
    mag = jnp.abs(u.astype(jnp.float32))
    thresh = jax.lax.top_k(mag, k)[0][-1]
    mask = mag >= thresh
    # tie-break: keep at most k (ties at threshold may exceed k; the paper's
    # torch impl keeps exactly k — we keep ties, a <1e-6 measure difference
    # documented in tests)
    return Compressed(jnp.where(mask, u, 0), mask)


def topk_compress_dynamic(u: jax.Array, k: jax.Array,
                          n_iters: int = 32) -> Compressed:
    """Top-K with a *traced* k (per-client BCRS ratios under vmap).

    Threshold bisection on the f32 *bit pattern* of |u|: non-negative IEEE
    floats order identically to their unsigned bit patterns, so bisecting the
    integer interval pins the exact k-th-largest magnitude in <= 32 halvings
    regardless of scale (a value-space bisection needs ~40 iterations and
    still loses exactness when the threshold is denormal-small, e.g. CR→1).
    The mask equals the exact ``|u| >= k-th largest`` selection (ties kept).

    This is the ONE Top-K selection in the tree — every engine (fused round,
    scanned simulation, mesh round, pod sync) routes here through
    ``repro.fed.engine``. Rank-agnostic: reductions run over ALL axes of
    ``u``, so a leaf in its natural (possibly TP-sharded) layout selects
    without being reshaped or gathered.
    """
    mag = jnp.abs(u.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(mag, jnp.uint32)
    hi = jnp.max(bits) + 1          # invariant: count(bits >= hi) < k
    lo = jnp.zeros_like(hi)         # invariant: count(bits >= lo) >= k

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo) >> 1)
        cnt = jnp.sum(bits >= mid)
        pred = cnt >= k
        return jnp.where(pred, mid, lo), jnp.where(pred, hi, mid)

    lo, _ = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    mask = bits >= lo
    return Compressed(jnp.where(mask, u, 0), mask)


# ------------------------------------------------- batched traced-k top-k
def topk_compress_batch(updates: jax.Array, ks: jax.Array) -> Compressed:
    """Per-row dynamic Top-K: updates [K, n], ks int32 [K] (traced).

    One trace serves every BCRS schedule — the per-client ``float(cr)``
    static-arg retrace this replaces cost O(rounds × K) XLA compiles."""
    return jax.vmap(topk_compress_dynamic)(updates, ks)


def ef_compress_batch(residuals: jax.Array, updates: jax.Array,
                      ks: jax.Array,
                      compress_batch: Callable = topk_compress_batch
                      ) -> Tuple[Compressed, jax.Array]:
    """Batched EF-TopK: bit-compatible with a per-client ``ef_compress``
    loop (same corrected/send/residual arithmetic, vectorized)."""
    corrected = residuals + updates
    comp = compress_batch(corrected, ks)
    return comp, corrected - comp.values


def randk_compress(u: jax.Array, cr: float, key) -> Compressed:
    n = u.shape[0]
    k = k_for_ratio(n, cr)
    idx = jax.random.choice(key, n, (k,), replace=False)
    mask = jnp.zeros((n,), bool).at[idx].set(True)
    # unbiased rand-k rescales by n/k
    return Compressed(jnp.where(mask, u * (n / k), 0), mask)


def quantize_stochastic(u: jax.Array, bits: int, key) -> jax.Array:
    """QSGD-style stochastic uniform quantization (dense; no mask)."""
    levels = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(u)) / levels
    scaled = u / jnp.maximum(scale, 1e-12)
    lower = jnp.floor(scaled)
    p = scaled - lower
    rnd = jax.random.uniform(key, u.shape)
    q = lower + (rnd < p)
    return q * scale


# ------------------------------------------------------------ error feedback
def ef_compress(residual: jax.Array, u: jax.Array, cr: float,
                compress=topk_compress) -> Tuple[Compressed, jax.Array]:
    """EF-TopK (EFSGD): accumulate residual, compress the corrected update,
    keep what was not sent. Returns (compressed, new_residual)."""
    corrected = residual + u
    comp = compress(corrected, cr)
    new_residual = corrected - comp.values
    return comp, new_residual


# ------------------------------------------------------------ sparse format
def to_sparse(comp: Compressed, k: int) -> Tuple[jax.Array, jax.Array]:
    """Dense-masked -> (indices i32 [k], values [k]) wire format. ``k`` must
    be static; entries beyond the actual retained count are index=-1."""
    mag = jnp.where(comp.mask, jnp.abs(comp.values.astype(jnp.float32)), -1.0)
    _, idx = jax.lax.top_k(mag, k)
    valid = jnp.take(comp.mask, idx)
    vals = jnp.take(comp.values, idx) * valid.astype(comp.values.dtype)
    return jnp.where(valid, idx, -1).astype(jnp.int32), vals


def from_sparse(indices: jax.Array, values: jax.Array, n: int) -> jax.Array:
    """(indices, values) -> dense [n]; index -1 entries dropped."""
    safe_idx = jnp.where(indices >= 0, indices, 0)
    contrib = jnp.where(indices >= 0, values, 0)
    return jnp.zeros((n,), values.dtype).at[safe_idx].add(contrib)
