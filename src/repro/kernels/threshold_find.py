"""Pallas TPU kernel: exact per-client k-th-magnitude thresholds at TRACED k.

The traced-k Top-K in ``core.compression.topk_compress_dynamic`` bisects the
uint32 bit pattern of |u| (non-negative IEEE floats order identically to
their bit patterns), but its XLA lowering re-reads the whole [C, n] magnitude
array on every one of its 32 halvings — ~32 HBM round-trips just to find the
thresholds. This kernel finds the SAME thresholds in ``SWEEPS`` = 8 logical
reads by widening the bisection to a 16-ary search.

Grid and blocks. The grid is ``(SWEEPS, cdiv(n, width))``; TPU grids iterate
the last axis innermost, so each sweep streams the [C, n] operand through
VMEM exactly once, in ``(C, width)`` blocks. ``block_lanes`` derives
``width`` from the call's shapes alone (C, n, and whether EF residuals are a
second input): about ``BLOCK_BYTES`` of input per grid step, so a round of
a 1.03e9-parameter model at C=4 pays the fixed cost of a grid step ~63 K
times. A leaf smaller than one block takes a single block.

Lane-dense vregs. The operand keeps its [C, n] HBM layout (a reshape to
[C, n/128, 128] would be a relayout copy on the TPU, not a view). Inside a
block, a loop walks the lanes ``fold * 128`` at a time and stacks the fold
lane-slices of the ``(C, 128)`` rows onto the sublanes (``fold = 8 // C``;
sublane ``k * C + c`` holds client c's lanes ``k * 128 ...``), so a small
cohort still fills its ``(8, 128)`` vregs: whole at C = 1, 2, 4 and 8.

Counts. For each of the W-1 = 15 candidate boundaries ``lo + j*step`` the
loop adds ``bits >= b_j`` into an int32 vreg accumulator of the stacked
shape: vreg adds only, no cross-lane reduction inside a step. The
accumulators live in VMEM scratch across the sweep's blocks (a per-lane
count is at most n / 128, so int32 cannot overflow) and are reduced once,
at the sweep's last block, to per-client counts [C, W-1]. The largest
qualifying boundary (count >= k) then becomes the new ``lo``: after 8 sweeps
the interval width is 1 and ``lo`` is exactly the k-th-largest bit pattern
(ties kept), bit-identical to the 32-halving reference for every k in
[1, n]. The interval width is client-independent (width_s = 2^31 / 16^s),
so the boundary spacing is recomputed from ``program_id(0)``; ``lo [C, 1]``
is carried in VMEM scratch across the whole grid.

Per-client retained counts ``ks [C, 1]`` arrive as one ``(C, 1)`` VMEM block
that every grid step maps to, so they stay fully traced — one compiled
kernel serves every BCRS schedule. (Mosaic loads only scalars from SMEM, so
a scalar-prefetch operand could not be compared against the ``[C, W-1]``
counts as a vector; Mosaic has no unsigned reductions, so the boundary
index is reduced in int32.) The optional ``e2d`` input switches the
selection quantity to the error-feedback ``corrected = residuals + updates``
without materializing it in HBM.

``emit_scale`` additionally returns the per-client row absmax
``max_j |corrected_ij|`` — the quantity a symmetric quantizer's scale is
derived from. It rides on sweep 0's streamed blocks (a running vreg max in
VMEM scratch, reduced to [C, 1] at sweep 0's last block), so it costs ZERO
extra HBM passes; fp max is exact and associative, so it is bit-identical to
``jnp.max(jnp.abs(corrected), axis=1)``. For Top-K selection this absmax IS
the survivors' absmax (k >= 1 keeps the largest magnitude, ties or not),
which is why the downstream codec kernel can use it as the jnp codec's
scale verbatim (docs/DESIGN.md §10).

Padding contract: tail lanes past the real ``n`` must be zero, and ``n`` a
multiple of ``TILE_N`` = 128. Candidate boundaries are always >= 1
(``step >= 1``, ``j >= 1``), so zero lanes are never counted and the
thresholds are those of the unpadded rows. A last block that overhangs the
operand holds undefined data past ``n`` on the TPU; its lanes are masked by
position to bit pattern 0, which the same argument covers.
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
LANES = 128
SUBLANES = 8
#: the operand's width must be a multiple of one vreg row
TILE_N = LANES
#: input bytes streamed per grid step (all inputs together, rows rounded up
#: to whole vregs); double-buffered that is 8 MiB of VMEM
BLOCK_BYTES = 4 << 20
#: folded chunks counted per iteration of the in-block loop
UNROLL = 16
#: initial boundary spacing: span 2^31 split into WAYS buckets
_STEP0 = np.uint32((1 << 31) // WAYS)


def _fold(c: int) -> int:
    """Lane-slices stacked onto the sublanes so C clients fill a vreg."""
    return max(1, SUBLANES // c)


def block_lanes(c: int, n: int, ef: bool) -> int:
    """Lanes of the ``(C, width)`` block each grid step streams: about
    ``BLOCK_BYTES`` of input, a multiple of 1024 lanes (so every folded
    chunk is whole), and no wider than the operand rounded up to 1024."""
    rows = -(-c // SUBLANES) * SUBLANES
    chunk = SUBLANES * LANES
    width = BLOCK_BYTES // ((2 if ef else 1) * rows * 4) // chunk * chunk
    return min(max(width, chunk), -(-n // chunk) * chunk)


def _threshold_find_kernel(has_res: bool, emit_scale: bool, n: int,
                           width: int, x_ref, *rest):
    rest = list(rest)
    e_ref = rest.pop(0) if has_res else None
    ks_ref = rest.pop(0)
    th_ref = rest.pop(0)
    sc_ref = rest.pop(0) if emit_scale else None
    lo_ref, acc_ref = rest[:2]
    mx_ref = rest[2] if emit_scale else None
    c = x_ref.shape[0]
    fold = _fold(c)
    lanes = fold * LANES                  # one client's lanes per chunk
    s = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(jnp.logical_and(s == 0, t == 0))
    def _():
        lo_ref[...] = jnp.zeros_like(lo_ref)

    @pl.when(t == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if emit_scale:
            mx_ref[...] = jnp.zeros_like(mx_ref)

    def load(ref, off):
        """Lanes [off, off + fold*128) of every client as (fold*C, 128):
        lane-slice k lands on sublanes k*C .. k*C + C - 1."""
        parts = [ref[:, pl.ds(off + k * LANES, LANES)].astype(jnp.float32)
                 for k in range(fold)]
        return parts[0] if fold == 1 else jnp.concatenate(parts, axis=0)

    stacked = (fold * c, LANES)
    # interval width is client-independent: width_s = 2^31 / 16^s, so the
    # boundary spacing needs no cross-sweep state (floor(ceil) identities:
    # widths are powers of two until the final width-8 -> step-1 sweep)
    step = jnp.maximum(_STEP0 >> (4 * s.astype(jnp.uint32)), jnp.uint32(1))
    lo = jnp.broadcast_to(lo_ref[...], (c, LANES))
    lo = lo if fold == 1 else jnp.concatenate([lo] * fold, axis=0)
    # the W-1 candidate boundaries lo + j*step, one stacked vreg each
    bounds = jnp.stack([lo + jnp.uint32(j) * step for j in range(1, WAYS)])

    # chunks of this block: the last block may overhang n, and then only
    # the remainder loop (which masks by position) reaches past its end
    full = width // lanes
    last = n - (-(-n // width) - 1) * width     # lanes of the last block
    masked = last != width
    if masked:
        # lane position within a chunk of each element of the stacked vregs
        row = jax.lax.broadcasted_iota(jnp.int32, stacked, 0)
        pos0 = jax.lax.broadcasted_iota(jnp.int32, stacked, 1)
        for k in range(1, fold):
            pos0 = pos0 + jnp.where(row >= k * c, LANES, 0)
        final = t == nt - 1
        groups = jnp.where(final, last // lanes // UNROLL, full // UNROLL)
        chunks = jnp.where(final, -(-last // lanes), full)
    else:
        groups, chunks = full // UNROLL, full

    def count(with_max: bool):
        def chunk(off, carry, mask):
            v = load(x_ref, off)
            if has_res:
                v = load(e_ref, off) + v
            a = jnp.abs(v)
            if mask:                      # lanes past n: undefined data
                a = jnp.where(t * width + off + pos0 < n, a, 0.0)
            bits = jax.lax.bitcast_convert_type(a, jnp.uint32)[None]
            acc = carry[0] + (bits >= bounds).astype(jnp.int32)
            return (acc, jnp.maximum(carry[1], a)) if with_max else (acc,)

        def unrolled(i, carry):
            base = pl.multiple_of(i * (UNROLL * lanes), UNROLL * lanes)
            for u in range(UNROLL):
                carry = chunk(base + u * lanes, carry, False)
            return carry

        def single(i, carry):
            return chunk(pl.multiple_of(i * lanes, lanes), carry, masked)

        carry = (acc_ref[...], mx_ref[...]) if with_max else (acc_ref[...],)
        carry = jax.lax.fori_loop(0, groups, unrolled, carry)
        carry = jax.lax.fori_loop(groups * UNROLL, chunks, single, carry)
        acc_ref[...] = carry[0]
        if with_max:
            mx_ref[...] = carry[-1]

    if emit_scale:
        @pl.when(s == 0)                  # the absmax rides on sweep 0
        def _():
            count(True)

        @pl.when(s > 0)
        def _():
            count(False)
    else:
        count(False)

    def per_client(ref, combine, reduce):
        """(fold*C, 128) stacked vregs -> [C, 1] per client."""
        rows = [ref[pl.ds(k * c, c), :] for k in range(fold)]
        return reduce(functools.reduce(combine, rows), axis=1, keepdims=True)

    @pl.when(t == nt - 1)
    def _():
        cnt = jnp.concatenate([per_client(acc_ref.at[j], jnp.add, jnp.sum)
                               for j in range(WAYS - 1)], axis=1)
        k = ks_ref[...]                                     # [C, 1] i32
        qual = cnt >= k                                     # [C, W-1]
        # the boundary index is reduced in int32: Mosaic has no unsigned
        # reductions (j <= 15, so the cast back is exact)
        jvec = jax.lax.broadcasted_iota(jnp.int32, (1, WAYS - 1), 1) + 1
        jsel = jnp.max(jnp.where(qual, jvec, 0),
                       axis=1, keepdims=True)               # [C, 1]
        new_lo = lo_ref[...] + jsel.astype(jnp.uint32) * step
        lo_ref[...] = new_lo

        @pl.when(s == SWEEPS - 1)
        def _():
            th_ref[...] = new_lo

        if emit_scale:
            @pl.when(s == 0)
            def _():
                sc_ref[...] = per_client(mx_ref, jnp.maximum, jnp.max)


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
    width = block_lanes(c, n, e2d is not None)
    bs = pl.BlockSpec((c, width), lambda s, t: (0, t))
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
    stacked = (_fold(c) * c, LANES)
    scratch = [pltpu.VMEM((c, 1), jnp.uint32),
               pltpu.VMEM((WAYS - 1,) + stacked, jnp.int32)]
    if emit_scale:
        scratch.append(pltpu.VMEM(stacked, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_threshold_find_kernel, e2d is not None,
                          emit_scale, n, width),
        grid=(SWEEPS, pl.cdiv(n, width)),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="threshold_find",
    )(*args)
    return (out[0], out[1]) if emit_scale else out
