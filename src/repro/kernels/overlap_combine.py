"""Pallas TPU kernel: fused OPWA aggregation (paper Alg. 1 line 17-18 +
Alg. 3) in a single HBM pass.

Per output tile of n: read all K clients' masked values + masks, compute
overlap counts, the gamma mask, and the coefficient-weighted sum — fused.
The unfused jnp path reads the K×n data three times (counts, weighted sum,
final multiply); this kernel reads it once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 1024


def _overlap_combine_kernel(gamma: float, d: int, vals_ref, masks_ref,
                            coeffs_ref, out_ref):
    vals = vals_ref[...].astype(jnp.float32)        # [K, T]
    masks = masks_ref[...].astype(jnp.int32)        # [K, T]
    coeffs = coeffs_ref[...].astype(jnp.float32)    # [K, 1]
    counts = jnp.sum(masks, axis=0, keepdims=True)  # [1, T]
    weighted = jnp.sum(vals * coeffs, axis=0, keepdims=True)
    amplify = (counts > 0) & (counts <= d)
    m = jnp.where(amplify, jnp.float32(gamma), jnp.float32(1.0))
    out_ref[...] = (m * weighted).astype(out_ref.dtype)


def overlap_combine_pallas(vals: jax.Array, masks: jax.Array,
                           coeffs: jax.Array, gamma: float, d: int,
                           *, interpret: bool = True) -> jax.Array:
    """vals: [K, n] f32; masks: [K, n] int8/bool; coeffs: [K] f32.

    n must be a multiple of TILE_N (pad in ops.py). Returns [1, n] f32."""
    k, n = vals.shape
    assert n % TILE_N == 0
    grid = (n // TILE_N,)
    kv = pl.BlockSpec((k, TILE_N), lambda i: (0, i))
    kc = pl.BlockSpec((k, 1), lambda i: (0, 0))
    out = pl.BlockSpec((1, TILE_N), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_overlap_combine_kernel, gamma, d),
        grid=grid,
        in_specs=[kv, kv, kc],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="overlap_combine",
    )(vals, masks.astype(jnp.int8), coeffs.reshape(k, 1))
