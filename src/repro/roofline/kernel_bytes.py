"""HBM-traffic accounting for the client-merge hot path: the traced-k Pallas
megakernel pipeline vs the unfused XLA lowering of ``compress_merge_leaf``.

Two complementary accountings, compared in ``BENCH_kernels.json``:

  * ``megakernel_hbm_bytes`` — the kernel pipeline's DMA traffic, computed
    analytically from its grid/block structure. Pallas fetches every
    declared input block and flushes every output block once per grid step,
    so the byte count is exact by construction (it is the same model
    ``pl.CostEstimate`` uses): threshold-find streams the [C, n] operands
    once per bisection sweep; fused-merge reads them once more and writes
    only the aggregate (plus the EF residual tile).

  * ``unfused_merge_bytes`` — the jnp path, measured from the compiled HLO
    via ``repro.roofline.hlo_cost.analyze_hlo``. XLA's own
    ``cost_analysis()`` counts while-loop bodies ONCE regardless of trip
    count, hiding 32x of the traced-k bisection's traffic — exactly the
    distortion hlo_cost exists to undo — so the trip-count-aware number is
    the honest unfused baseline. The uncorrected ``cost_analysis`` number is
    reported alongside it for transparency.

Both accountings are per logical execution of the merge (one cohort, one
round) on one device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import strategies as strat_mod
from repro.roofline.hlo_cost import analyze_hlo

_F32 = 4
_I32 = 4
_U32 = 4


def _pad_to(n: int, tile: int) -> int:
    return n + ((-n) % tile)


def megakernel_hbm_bytes(c: int, n: int, strategy: str) -> dict:
    """Analytic DMA bytes of the two-kernel pipeline for one [C, n] merge.

    Returns ``{"threshold", "merge", "total", "passes"}`` where ``passes``
    is total / (C*n*4) — logical full reads of the update matrix.

    The strategy's registered capabilities drive the accounting: the EF
    residual stream follows ``needs_residuals``, the codec scale streams
    (threshold-find's [C, 1] absmax write, fused-merge's [C, 1] scales
    read) follow ``kernel_codec``, and strategies that declare
    ``megakernel=False`` (dense exchange, or codecs without a registered
    kernel lowering) are rejected rather than priced with a model that does
    not match their lowering.
    """
    from repro.kernels.fused_merge import TILE_N as MERGE_TILE
    from repro.kernels.threshold_find import SWEEPS
    strat = strat_mod.get(strategy)
    if not strat.megakernel:
        raise ValueError(
            f"strategy {strategy!r} does not route through the megakernel "
            f"pipeline (megakernel=False); its traffic is not modeled here")
    ef = strat.needs_residuals
    codec = strat.kernel_codec is not None
    n_pad = _pad_to(n, MERGE_TILE)  # one padding serves both kernels
    mat = c * n_pad * _F32
    n_ops = 2 if ef else 1          # (updates[, residuals]) streamed tiles
    # threshold-find: every sweep streams the [C, n] operand tiles; the
    # [C, 1] ks/lo/threshold scalars ride along once per grid step
    thresh = SWEEPS * n_ops * mat + c * (_I32 + _U32)
    if codec:
        thresh += c * _F32          # [C, 1] absmax (the quantizer scale)
    # fused merge: one read of the operands + per-grid-step [C, 1] columns,
    # one write of the [1, n] aggregate (+ the [C, n] EF residual update)
    merge = n_ops * mat + n_pad * _F32 + c * (_U32 + 2 * _F32)
    if codec:
        merge += c * _F32           # [C, 1] scales column read
    if ef:
        merge += mat                # new_residuals write
    total = thresh + merge
    return {"threshold": float(thresh), "merge": float(merge),
            "total": float(total), "passes": total / (c * n * _F32)}


def wire_stream_bytes(strategy: str, n: int, k: int) -> dict:
    """Bytes-on-the-wire pricing of one client's upload under the
    strategy's registered ``WireFormat``, against the idx32+f32 reference
    pair (8 B/survivor).

    ``pair_ratio`` is the PER-SURVIVOR value+index stream ratio — the
    number the packed formats are judged on (int8: (4+1)/8 = 5/8; int4:
    (4+0.5)/8 = 9/16); the per-message scale rides in ``overhead_bytes``
    and is amortized over k in ``total_ratio`` (a bitmask stream, priced
    per coordinate, lands there too).
    """
    wire = strat_mod.get(strategy).wire
    if wire.dense:
        raise ValueError(
            f"strategy {strategy!r} exchanges dense tensors; survivor-"
            "stream pricing is meaningless (see cost_model."
            "uncompressed_round)")
    ref_pair = 8.0                  # idx32 + f32
    pair = wire.index_bytes + wire.value_bytes
    total = wire.bytes_on_wire(n, k)
    return {"kind": wire.kind,
            "pair_bytes": pair,
            "pair_ratio": pair / ref_pair,
            "overhead_bytes": wire.overhead_bytes,
            "mask_bits": wire.mask_bits,
            "bytes_on_wire": float(total),
            "ref_bytes": ref_pair * k,
            "total_ratio": float(total) / (ref_pair * k)}


def unfused_merge_bytes(spec, c: int, n: int) -> dict:
    """Trip-count-aware HBM bytes of the unfused (jnp) ``compress_merge_leaf``
    lowering for a [C, n] merge, plus XLA's uncorrected ``cost_analysis``
    number. ``spec``: a ``fed.engine.ClientUpdateSpec``; its ``use_kernel``
    is ignored — the baseline is always the jnp route.
    """
    from repro.fed.engine import compress_merge_leaf
    u = jnp.zeros((c, n), jnp.float32)
    w = jnp.ones((c,), jnp.float32) / c
    ks = jnp.ones((c,), jnp.int32)
    r = jnp.zeros((c, n), jnp.float32) if spec.needs_residuals else None
    fn = jax.jit(lambda u, w, ks, r: compress_merge_leaf(
        u, w, ks, spec.strat, gamma=spec.gamma, overlap_d=spec.overlap_d,
        use_kernel=False, residuals=r))
    compiled = fn.lower(u, w, ks, r).compile()
    cost = analyze_hlo(compiled.as_text(), 1)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    return {"total": float(cost.bytes),
            "passes": cost.bytes / (c * n * _F32),
            "xla_cost_analysis": xla_bytes,
            "xla_cost_analysis_passes": xla_bytes / (c * n * _F32)}


def merge_traffic_ratio(spec, c: int, n: int) -> dict:
    """unfused / kernel HBM-byte ratio for one [C, n] merge (>= 3x is the
    acceptance bar for the megakernel pipeline)."""
    kern = megakernel_hbm_bytes(c, n, spec.strategy)
    base = unfused_merge_bytes(spec, c, n)
    return {"c": c, "n": n, "strategy": spec.strategy,
            "kernel": kern, "unfused": base,
            "ratio": base["total"] / kern["total"]}
