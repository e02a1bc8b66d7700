"""Pallas TPU kernel: exact per-client k-th-magnitude thresholds at TRACED k.

The traced-k Top-K in ``core.compression.topk_compress_dynamic`` bisects the
uint32 bit pattern of |u| (non-negative IEEE floats order identically to
their bit patterns), but its XLA lowering re-reads the whole [C, n] magnitude
array on every one of its 32 halvings — ~32 HBM round-trips just to find the
thresholds. This kernel finds the SAME thresholds in ``SWEEPS`` = 8 logical
reads by widening the bisection to a 16-ary search:

  * the grid is (SWEEPS, n_tiles); TPU grids iterate the last axis innermost,
    so each sweep streams every n-tile through VMEM exactly once;
  * per-client interval state ``lo [C, 1]`` lives in VMEM scratch across the
    whole grid; the interval width is uniform across clients and depends only
    on the sweep index (width_s = 2^31 / 16^s), so it is recomputed from
    ``program_id(0)`` instead of being carried;
  * each tile accumulates per-client counts of ``bits >= lo + j*step`` for
    the W-1 = 15 candidate boundaries into a [C, W-1] VMEM accumulator
    (hierarchical count reduction: tile-local compare+sum, cross-tile add);
  * at the sweep's last tile the largest qualifying boundary (count >= k)
    becomes the new ``lo`` — after 8 sweeps the interval width is 1 and
    ``lo`` is exactly the k-th-largest bit pattern (ties kept), bit-identical
    to the 32-halving reference for every k in [1, n].

Per-client retained counts ``ks [C, 1]`` arrive as one ``(C, 1)`` VMEM block
that every grid step maps to, so they stay fully traced — one compiled
kernel serves every BCRS schedule. (Mosaic loads only scalars from SMEM, so
a scalar-prefetch operand could not be compared against the ``[C, W-1]``
counts as a vector.) The optional ``e2d`` input switches the selection
quantity to the error-feedback ``corrected = residuals + updates`` without
materializing it in HBM.

``emit_scale`` additionally returns the per-client row absmax
``max_j |corrected_ij|`` — the quantity a symmetric quantizer's scale is
derived from. It rides on sweep 0's existing streamed tiles (a running
max-of-tile-maxes in the output's VMEM block), so it costs ZERO extra HBM
passes; fp max is exact and associative, so the tile-wise accumulation is
bit-identical to ``jnp.max(jnp.abs(corrected), axis=1)``. For Top-K
selection this absmax IS the survivors' absmax (k >= 1 keeps the largest
magnitude, ties or not), which is why the downstream codec kernel can use
it as the jnp codec's scale verbatim (docs/DESIGN.md §10).

Padding contract: tail lanes past the real ``n`` must be zero. Candidate
boundaries are always >= 1 (``step >= 1``, ``j >= 1``), so zero-padded lanes
can never be counted and the thresholds are those of the unpadded rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: 16-ary search: 15 candidate boundaries per sweep, 8 sweeps cover the full
#: 2^31 span of |f32| bit patterns (16^8 = 2^32), ending at interval width 1.
WAYS = 16
SWEEPS = 8
TILE_N = 512
#: initial boundary spacing: span 2^31 split into WAYS buckets
_STEP0 = np.uint32((1 << 31) // WAYS)


def _threshold_find_kernel(has_res: bool, emit_scale: bool, x_ref, *rest):
    rest = list(rest)
    e_ref = rest.pop(0) if has_res else None
    ks_ref = rest.pop(0)
    th_ref = rest.pop(0)
    sc_ref = rest.pop(0) if emit_scale else None
    lo_ref, cnt_ref = rest
    if has_res:
        corrected = (e_ref[...].astype(jnp.float32)
                     + x_ref[...].astype(jnp.float32))
    else:
        corrected = x_ref[...].astype(jnp.float32)
    s = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    bits = jax.lax.bitcast_convert_type(jnp.abs(corrected), jnp.uint32)

    if emit_scale:
        # per-client absmax accumulated over sweep 0's tiles only — the
        # operand stream is already paid for, and the output block maps to
        # (0, 0) for every grid step so the running max persists in VMEM
        @pl.when(s == 0)
        def _():
            tilemax = jnp.max(jnp.abs(corrected), axis=1, keepdims=True)
            prev = jnp.where(t == 0, jnp.float32(0.0), sc_ref[...])
            sc_ref[...] = jnp.maximum(prev, tilemax)

    @pl.when(jnp.logical_and(s == 0, t == 0))
    def _():
        lo_ref[...] = jnp.zeros_like(lo_ref)

    @pl.when(t == 0)
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # interval width is client-independent: width_s = 2^31 / 16^s, so the
    # boundary spacing needs no cross-sweep state (floor(ceil) identities:
    # widths are powers of two until the final width-8 -> step-1 sweep)
    step = jnp.maximum(_STEP0 >> (4 * s.astype(jnp.uint32)), jnp.uint32(1))
    lo = lo_ref[...]                                        # [C, 1] u32

    # hierarchical count: tile-local compare+sum per candidate boundary,
    # accumulated across tiles in VMEM (W-1 static columns, unrolled)
    cols = []
    for j in range(1, WAYS):
        b_j = lo + jnp.uint32(j) * step                     # [C, 1]
        cols.append(jnp.sum((bits >= b_j).astype(jnp.int32),
                            axis=1, keepdims=True))
    cnt_ref[...] += jnp.concatenate(cols, axis=1)           # [C, W-1]

    @pl.when(t == nt - 1)
    def _():
        cnt = cnt_ref[...]
        k = ks_ref[...]                                     # [C, 1] i32
        qual = cnt >= k
        # the boundary index is reduced in int32: Mosaic has no unsigned
        # reductions (j <= 15, so the cast back is exact)
        jvec = jax.lax.broadcasted_iota(jnp.int32, (1, WAYS - 1), 1) + 1
        jsel = jnp.max(jnp.where(qual, jvec, 0),
                       axis=1, keepdims=True)               # [C, 1]
        new_lo = lo + jsel.astype(jnp.uint32) * step
        lo_ref[...] = new_lo

        @pl.when(s == SWEEPS - 1)
        def _():
            th_ref[...] = new_lo


def threshold_find_pallas(x2d: jax.Array, ks: jax.Array,
                          e2d: jax.Array | None = None,
                          *, emit_scale: bool = False,
                          interpret: bool = True):
    """x2d: [C, n] f32 (n % TILE_N == 0, zero-padded tail); ks: [C, 1] i32
    traced retained counts (1 <= k <= real n); e2d: optional matching EF
    residuals — thresholds are then those of ``e2d + x2d``.

    Returns the k-th-largest |.| bit patterns as uint32 [C, 1]: the exact
    Top-K mask is ``bitcast(|x|) >= thresholds`` (ties kept), matching
    ``topk_compress_dynamic`` bit for bit. With ``emit_scale`` returns
    ``(thresholds, absmax [C, 1] f32)`` — the per-client
    ``max |corrected|``, bit-identical to the jnp row max (see module
    docstring), free-riding on sweep 0's operand stream.
    """
    c, n = x2d.shape
    assert n % TILE_N == 0, f"n={n} must be a multiple of {TILE_N}"
    nt = n // TILE_N
    bs = pl.BlockSpec((c, TILE_N), lambda s, t: (0, t))
    col = pl.BlockSpec((c, 1), lambda s, t: (0, 0))
    in_specs, args = [bs], [x2d]
    if e2d is not None:
        in_specs.append(bs)
        args.append(e2d)
    in_specs.append(col)
    args.append(ks.astype(jnp.int32))
    out_specs = [col, col] if emit_scale else col
    out_shape = jax.ShapeDtypeStruct((c, 1), jnp.uint32)
    if emit_scale:
        out_shape = [out_shape, jax.ShapeDtypeStruct((c, 1), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_threshold_find_kernel, e2d is not None,
                          emit_scale),
        grid=(SWEEPS, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((c, 1), jnp.uint32),
                        pltpu.VMEM((c, WAYS - 1), jnp.int32)],
        interpret=interpret,
        name="threshold_find",
    )(*args)
    return (out[0], out[1]) if emit_scale else out
