"""Pallas TPU kernel: fused EF-TopK step.

    corrected = residual + g
    mask      = block-top-k(|corrected|)
    send      = corrected ⊙ mask
    residual' = corrected − send

Unfused this is >= 3 HBM round-trips over the gradient; fused it is one read
of (g, residual) and one write of (send, residual'). Threshold selection
reuses the bisection from block_topk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_TILE = 8
N_ITERS = 40


def _ef_update_kernel(k: int, g_ref, e_ref, send_ref, newe_ref):
    corrected = (e_ref[...].astype(jnp.float32)
                 + g_ref[...].astype(jnp.float32))
    mag = jnp.abs(corrected)
    hi = jnp.max(mag, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.int32), axis=1, keepdims=True)
        pred = cnt >= k
        return jnp.where(pred, mid, lo), jnp.where(pred, hi, mid)

    lo, _ = jax.lax.fori_loop(0, N_ITERS, body, (lo, hi))
    mask = mag >= lo
    send = jnp.where(mask, corrected, 0.0)
    send_ref[...] = send.astype(send_ref.dtype)
    newe_ref[...] = (corrected - send).astype(newe_ref.dtype)


def ef_update_pallas(g2d: jax.Array, e2d: jax.Array, k: int,
                     *, interpret: bool = True):
    """g2d, e2d: [nb, block]. Returns (send, new_residual), both f32."""
    nb, block = g2d.shape
    assert block % 128 == 0 and nb % ROWS_TILE == 0
    grid = (nb // ROWS_TILE,)
    bs = pl.BlockSpec((ROWS_TILE, block), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_ef_update_kernel, k),
        grid=grid,
        in_specs=[bs, bs],
        out_specs=[bs, bs],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.float32),
                   jax.ShapeDtypeStruct((nb, block), jnp.float32)],
        interpret=interpret,
        name="ef_update",
    )(g2d, e2d)
