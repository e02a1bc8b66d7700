"""Pallas TPU megakernel: fused traced-k apply + merge for the client-update
hot path (paper Alg. 1 lines 14-18 in ONE HBM pass).

Given the per-client k-th-magnitude thresholds from ``threshold_find``, the
unfused XLA path still makes 4-6 more full passes over the [C, n] update
matrix: EF correction, mask materialization, masked values, overlap counts,
the coefficient-weighted sum, and the OPWA multiply each round-trip HBM.
This kernel reads each (updates, residuals) lane once and produces, per
chunk of lanes and entirely in VMEM:

    corrected = residuals + updates          (EF configs)
    mask      = bitcast(|corrected|) >= threshold   (ties kept)
    send      = corrected . mask             (x active-row gating)
    send      = dequant(quant(send, scale))  (codec configs: int8/int4 grid)
    counts    = sum_c mask                   (degree of overlap)
    M         = gamma where 0 < counts <= D else 1   (OPWA, Alg. 3)
    agg       = M . sum_c w_c * send         (coefficient-weighted merge)
    residual' = corrected - send             (inactive rows pass through)

writing only the aggregate [1, n] (plus the residuals for EF configs)
back to HBM: the Top-K selection, the EF arithmetic and the OPWA merge
at traced per-client k, in one pass over each block, every intermediate
kept in VMEM.

The codec stage (``codec="int8"|"int4"``) quantizes the send values onto the
symmetric integer grid with the per-client ``scales`` column (derived from
``threshold_find``'s row absmax — for Top-K the survivors' absmax equals
the row absmax, so it costs no extra pass) and merges the DEQUANTIZED
values; ``residual' = corrected - dequant(send)`` makes EF absorb the
quantization error. The quantize->dequantize op sequence is
``core.strategies.symmetric_dequantize`` — literally the same function the
jnp ``value_codec`` path runs — so the two routes are bit-exact per lane
(docs/DESIGN.md §10).

Grid and blocks. The grid is ``(cdiv(n, width),)``: each grid step streams
a ``(C, width)`` block of the updates (and of the residuals) through VMEM
and writes the ``(1, width)`` aggregate block (and the ``(C, width)``
residual block). ``block_lanes`` derives ``width`` from the call's shapes
alone (C, n, and whether EF residuals are a second input): about
``BLOCK_BYTES`` of input per grid step, so a round of a 1.03e9-parameter
model at C=4 pays the fixed cost of a grid step ~7.8 K times; double
buffered, inputs and outputs stay inside Mosaic's default scoped VMEM. A
leaf smaller than one block takes a single block. Inside a block, a loop
walks ``TILE_N`` = 1024 lanes an iteration as ``UNROLL`` ``(C, CHUNK)``
slices; each slice runs the sequence above on its lanes and stores its
slice of the outputs, so no block-sized intermediate is materialized.

Padding and overhang. The wrapper zero-pads n to a multiple of
``TILE_N``, so every block holds whole loop iterations. The last block
may overhang the padded operand; its loop stops at the operand's end, so
the undefined lanes a TPU loads past it are never read, and the output
lanes past it are never written (nor copied back).

Bit-exactness contract (asserted in tests/test_megakernel.py on the CPU and
by chip_smoke.py on a TPU): every intermediate uses the same op sequence as
the jnp route of ``fed.engine.compress_merge_leaf`` — so agg and residuals
match the traced jnp path bit for bit, per lane, including the all-True tie
masks of all-zero rows. The weighted sum is an f32 multiply and a sum over
the client axis: XLA on a TPU lowers the reference's ``tensordot`` (at any
matmul precision) to exactly that,
while a Mosaic ``dot_general`` runs on the MXU and rounds differently at
DEFAULT and HIGHEST precision alike (measured on a v5e at C=2 and C=8).

``active`` gating mirrors the engine's padded-cohort semantics: inactive
rows contribute nothing to the merge or the overlap counts and their
residuals pass through unchanged; it is a multiply by exactly 1.0/0.0, so
fully-active cohorts are bit-identical to the ungated arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the quantize->dequantize op sequence shared with the jnp value_codec path
# (strategies imports only jnp — no cycle)
from repro.core.strategies import CODEC_LEVELS, symmetric_dequantize

SUBLANES = 8
#: the operand's width is padded to a multiple of one loop iteration's lanes
TILE_N = 1024
#: lanes of the (C, CHUNK) slice each step of the in-block loop computes;
#: UNROLL of them make one iteration of TILE_N lanes. One vreg column per
#: slice ran fastest on a v5e (256, 512 and 1024 lanes all ran slower)
CHUNK = 128
UNROLL = TILE_N // CHUNK
#: input bytes streamed per grid step (rows rounded up to whole vregs)
BLOCK_BYTES = 4 << 20


def block_lanes(c: int, n: int, ef: bool) -> int:
    """Lanes of the ``(C, width)`` block each grid step streams: about
    ``BLOCK_BYTES`` of input, a multiple of ``TILE_N``, and no wider than
    the operand rounded up to ``TILE_N``."""
    rows = -(-c // SUBLANES) * SUBLANES
    width = BLOCK_BYTES // ((2 if ef else 1) * rows * 4) // TILE_N * TILE_N
    return min(max(width, TILE_N), -(-n // TILE_N) * TILE_N)


def _fused_merge_kernel(ef: bool, opwa: bool, gamma: float, d: int,
                        has_active: bool, codec: str, n: int, width: int,
                        *refs):
    refs = list(refs)
    x_ref = refs.pop(0)
    e_ref = refs.pop(0) if ef else None
    th_ref = refs.pop(0)
    w_ref = refs.pop(0)
    sc_ref = refs.pop(0) if codec != "none" else None
    act_ref = refs.pop(0) if has_active else None
    agg_ref = refs.pop(0)
    newres_ref = refs.pop(0) if ef else None
    c = x_ref.shape[0]

    # the [C, 1] columns, broadcast once per block to the chunk's shape
    col = lambda ref: jnp.broadcast_to(ref[...], (c, CHUNK))  # noqa: E731
    th, w = col(th_ref), col(w_ref)
    sc = col(sc_ref) if codec != "none" else None
    act = col(act_ref) if has_active else None

    def chunk(off):
        lanes = pl.ds(off, CHUNK)
        x = x_ref[:, lanes].astype(jnp.float32)             # [C, CHUNK]
        e = e_ref[:, lanes].astype(jnp.float32) if ef else None
        corrected = e + x if ef else x
        bits = jax.lax.bitcast_convert_type(jnp.abs(corrected), jnp.uint32)
        mask = bits >= th
        vals = jnp.where(mask, corrected, jnp.float32(0.0))
        if codec != "none":
            # the jnp codec's exact op sequence on the jnp codec's exact
            # scale (absmax/levels, a [C, 1] column) — survivors land on
            # the integer grid, non-survivors stay exactly zero, all-zero
            # rows keep scale 0 and dequantize to exact zeros
            vals = symmetric_dequantize(vals, sc, CODEC_LEVELS[codec])

        if ef:
            new_res = corrected - vals
            if has_active:
                new_res = jnp.where(act > jnp.float32(0.5), new_res, e)
            newres_ref[:, lanes] = new_res
        if has_active:
            # padded rows are all-zero updates whose tie-at-zero Top-K mask
            # is all-True — gate them out of the merge and the overlap
            # counts
            vals = vals * act
            mask = mask & (act > jnp.float32(0.5))

        # multiply and sum over clients, not a dot: see the module docstring
        weighted = jnp.sum(w * vals, axis=0, keepdims=True)  # [1, CHUNK]
        if opwa:
            counts = jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)
            amplify = (counts > 0) & (counts <= d)
            m = jnp.where(amplify, jnp.float32(gamma), jnp.float32(1.0))
            weighted = m * weighted
        agg_ref[:, lanes] = weighted

    def step(i, carry):
        base = pl.multiple_of(i * TILE_N, TILE_N)
        for u in range(UNROLL):
            chunk(base + u * CHUNK)
        return carry

    # the last block may overhang n: its loop stops at n (module docstring)
    full = width // TILE_N
    last = (n - (-(-n // width) - 1) * width) // TILE_N
    if last == full:
        steps = full
    else:
        final = pl.program_id(0) == pl.num_programs(0) - 1
        steps = jnp.where(final, last, full)
    jax.lax.fori_loop(0, steps, step, 0)


def fused_merge_pallas(x2d: jax.Array, thresholds: jax.Array,
                       weights: jax.Array,
                       e2d: jax.Array | None = None,
                       active: jax.Array | None = None,
                       *, opwa: bool = False, gamma: float = 1.0, d: int = 1,
                       codec: str = "none",
                       scales: jax.Array | None = None,
                       interpret: bool = True):
    """x2d: [C, n] f32 (any n — a ragged tail is zero-padded internally and
    the outputs sliced back); thresholds: [C, 1] uint32 bit-pattern
    thresholds (from ``threshold_find_pallas``); weights: [C, 1] f32 merge
    coefficients; e2d: optional EF residuals [C, n]; active: optional
    [C, 1] f32 row gate (exactly 1.0 / 0.0); codec + scales: optional
    quantization stage — scales [C, 1] f32 per-client symmetric grid scales
    (``strategies.quantization_scale`` of ``threshold_find``'s absmax; its
    mantissa rounding makes every dequantization product exact, so the EF
    subtraction below is immune to fma contraction).

    Zero padding is safe under every config: padded lanes have
    corrected == 0, so whatever the mask decides there (an all-True tie at
    a zero threshold included) contributes exactly-zero values, the codec
    maps them back to zero, overlap counts are per-lane, and the padded agg
    and residual lanes are sliced off before returning.

    Returns agg [1, n] f32, or (agg, new_residuals [C, n]) when ``e2d`` is
    given.
    """
    c, n = x2d.shape
    if codec != "none":
        assert codec in CODEC_LEVELS, f"unknown codec {codec!r}"
        assert scales is not None, "codec needs per-client scales"
        assert e2d is not None, (
            "codec without EF residuals silently drops the quantization "
            "error (same contract the strategy registry enforces)")
    n_pad = (-n) % TILE_N
    if n_pad:
        x2d = jnp.pad(x2d, ((0, 0), (0, n_pad)))
        if e2d is not None:
            e2d = jnp.pad(e2d, ((0, 0), (0, n_pad)))
    np_ = n + n_pad
    ef = e2d is not None
    has_active = active is not None
    width = block_lanes(c, np_, ef)
    block = pl.BlockSpec((c, width), lambda t: (0, t))
    col = pl.BlockSpec((c, 1), lambda t: (0, 0))

    in_specs, args = [block], [x2d]
    if ef:
        in_specs.append(block)
        args.append(e2d)
    in_specs += [col, col]
    args += [thresholds, weights.astype(jnp.float32)]
    if codec != "none":
        in_specs.append(col)
        args.append(scales.astype(jnp.float32))
    if has_active:
        in_specs.append(col)
        args.append(active.astype(jnp.float32))

    out_specs = [pl.BlockSpec((1, width), lambda t: (0, t))]
    out_shape = [jax.ShapeDtypeStruct((1, np_), jnp.float32)]
    if ef:
        out_specs.append(block)
        out_shape.append(jax.ShapeDtypeStruct((c, np_), jnp.float32))

    out = pl.pallas_call(
        functools.partial(_fused_merge_kernel, ef, opwa, float(gamma),
                          int(d), has_active, codec, np_, width),
        grid=(pl.cdiv(np_, width),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_merge",
    )(*args)
    if n_pad:
        if ef:
            return out[0][:, :n], out[1][:, :n]
        return out[0][:, :n]
    return (out[0], out[1]) if ef else out[0]
