"""Pallas TPU kernel: per-block magnitude Top-K selection.

TPU adaptation of the GPU radix-select: each VMEM-resident block finds its
k-th-largest magnitude by threshold *bisection* (40 fixed iterations — the
interval shrinks below one f32 ULP, so the mask equals the exact
``mag >= kth_largest`` selection, ties kept). No sort, no gather; pure
vector compares + reductions, one HBM read + one write per element.

Layout: x is reshaped to [nb, block] rows; grid tiles rows at ROWS_TILE=8
(f32 sublane) × block lanes (multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_TILE = 8
N_ITERS = 40


def _block_topk_kernel(k: int, x_ref, vals_ref, mask_ref):
    x = x_ref[...]
    mag = jnp.abs(x.astype(jnp.float32))
    hi = jnp.max(mag, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.int32), axis=1, keepdims=True)
        pred = cnt >= k
        lo = jnp.where(pred, mid, lo)
        hi = jnp.where(pred, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, N_ITERS, body, (lo, hi))
    mask = mag >= lo
    vals_ref[...] = jnp.where(mask, x, 0).astype(vals_ref.dtype)
    mask_ref[...] = mask.astype(jnp.int8)


def block_topk_pallas(x2d: jax.Array, k: int, *, interpret: bool = True):
    """x2d: [nb, block] (block % 128 == 0, nb % ROWS_TILE == 0).

    Returns (values [nb, block], mask int8 [nb, block])."""
    nb, block = x2d.shape
    assert block % 128 == 0, f"block={block} must be lane-aligned (128)"
    assert nb % ROWS_TILE == 0, f"nb={nb} must be a multiple of {ROWS_TILE}"
    grid = (nb // ROWS_TILE,)
    bs = pl.BlockSpec((ROWS_TILE, block), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_block_topk_kernel, k),
        grid=grid,
        in_specs=[bs],
        out_specs=[bs, bs],
        out_shape=[jax.ShapeDtypeStruct((nb, block), x2d.dtype),
                   jax.ShapeDtypeStruct((nb, block), jnp.int8)],
        interpret=interpret,
        name="block_topk",
    )(x2d)
