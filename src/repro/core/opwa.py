"""Overlap-aware Parameter Weighted Averaging (paper §4.3, Alg. 3).

Degree of overlap of parameter j = number of selected clients whose
sparsified update retained index j. Indices with overlap in (0, D] get their
aggregated update scaled by the enlarge rate gamma; everything else by 1.

The server update (Alg. 1 line 18):
    w_{t+1} = w_t - eta * sum_i p'_i * M ⊙ Δw_i^sparse
with M shared across clients, so aggregation fuses into a single masked
weighted sum. ``opwa_aggregate`` is the jnp reference; the engines' kernel
route computes the same merge inside ``kernels.fused_merge``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def overlap_counts(masks: jax.Array) -> jax.Array:
    """masks: bool/int [K, n] (K clients) -> int32 counts [n]."""
    return jnp.sum(masks.astype(jnp.int32), axis=0)


def opwa_mask(counts: jax.Array, gamma: float, d: int = 1) -> jax.Array:
    """M[j] = gamma if 0 < counts[j] <= D else 1 (f32 [n])."""
    amplify = (counts > 0) & (counts <= d)
    return jnp.where(amplify, jnp.float32(gamma), jnp.float32(1.0))


def overlap_histogram(masks: jax.Array, k_max: Optional[int] = None
                      ) -> jax.Array:
    """Counts-of-counts for the paper's Fig. 4 (degree-of-overlap dist).

    One ``bincount`` reduction (single pass over counts) instead of K+1
    masked sums; degrees above ``k_max`` are dropped, as before."""
    counts = overlap_counts(masks)
    k_max = k_max or masks.shape[0]
    return jnp.bincount(counts.reshape(-1), length=k_max + 1)


def opwa_aggregate(updates: jax.Array, masks: jax.Array, coeffs: jax.Array,
                   gamma: float, d: int = 1) -> jax.Array:
    """Fused OPWA aggregation (rank-agnostic, pure jnp).

    updates: [K, *shape] dense-masked sparse updates (flat [K, n] from the
    round engines, natural possibly-sharded leaf layout from the mesh/pod
    adapters); masks: matching bool; coeffs: [K] client coefficients p'_i.
    Returns M ⊙ Σ_i p'_i u_i  [*shape].
    """
    counts = overlap_counts(masks)
    m = opwa_mask(counts, gamma, d)
    weighted = jnp.tensordot(coeffs.astype(jnp.float32),
                             updates.astype(jnp.float32), axes=(0, 0))
    return m * weighted


def bcrs_aggregate(updates: jax.Array, coeffs: jax.Array) -> jax.Array:
    """BCRS-only aggregation (uniform parameter weights)."""
    return jnp.einsum("k,kn->n", coeffs.astype(jnp.float32),
                      updates.astype(jnp.float32))
