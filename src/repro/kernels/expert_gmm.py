"""Pallas TPU grouped matmuls of the dropless expert layer (``models/moe``).

The expert layer sorts the token rows routed to the experts held here by
expert: group ``g`` is the rows ``starts[g] .. starts[g] + sizes[g]``, the
groups in row order and disjoint. Rows in no group (the slots routed to
experts held elsewhere) cost nothing. Two kernels, adapted from the
megablox ``gmm``/``tgmm`` that ship with jax
(``jax.experimental.pallas.ops.tpu.megablox``), compute:

* ``expert_gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g % G]`` (or
  ``@ rhs[g % G].T``), ``[m, k] x [G, k, n] -> [m, n]``: the forward
  products and the gradient of the rows;
* ``expert_tgmm``: ``out[g] = lhs[rows of g].T @ dy[rows of g]``,
  ``[m, k] x [m, n] -> [groups, k, n]``: the gradient of the weights.

Several clients' rows stacked one after another are groups of one call
(``rhs[g % G]``: they share the weights, or each has its own when ``rhs``
holds every client's). The grid walks the row tiles the groups touch, a
traced bound; a tile shared by two groups is visited once per group and
each visit stores only its own group's rows. The wrappers in
``kernels/ops.py`` zero what the kernels leave unwritten.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the kernels may use: double-buffered blocks plus the accumulator of
#: the largest tiling (tm 512, a whole 1408 or 2048 k or n) stay under it
VMEM_LIMIT = 64 * 1024 * 1024


def row_tile(m: int) -> int:
    """The row tile: the largest of 512 .. 8 that divides ``m``."""
    for t in (512, 256, 128, 64, 32, 16, 8):
        if m % t == 0:
            return t
    raise ValueError(f"{m} rows are not a multiple of 8")


def col_tile(n: int) -> int:
    """A k or n tile: 512 where it divides, else the whole dimension."""
    return 512 if n % 512 == 0 else n


def group_metadata(starts: jax.Array, sizes: jax.Array, m: int, tm: int,
                   visit_empty: bool):
    """(starts, ends, group of each grid step, row tile of each grid step),
    and the number of grid steps, for ``m`` rows in tiles of ``tm``.

    Each group visits the row tiles its rows touch, in row order, so a tile
    is only ever revisited by the next grid step; with ``visit_empty`` an
    empty group visits the tile it would start in once (its output is then
    zeroed). Both step arrays are padded to their static bound."""
    g = sizes.shape[0]
    starts = starts.astype(jnp.int32)
    ends = starts + sizes.astype(jnp.int32)
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 0)
    if visit_empty:
        tiles = jnp.maximum(tiles, 1)
        first = jnp.minimum(first, m // tm - 1)
    bound = m // tm + g - 1 + (g if visit_empty else 0)
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=bound)
    step = jnp.arange(bound, dtype=jnp.int32)
    before = jnp.cumsum(tiles) - tiles
    m_tiles = first[group_ids] + step - before[group_ids]
    return (starts, ends, group_ids, m_tiles.astype(jnp.int32)), \
        jnp.sum(tiles)


def _rows_mask(meta, step, tm: int, width: int):
    """[tm, width] bool: the tile's rows that belong to the step's group."""
    starts, ends, group_ids, m_tiles = meta
    gid = group_ids[step]
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0) \
        + m_tiles[step] * tm
    return (row >= starts[gid]) & (row < ends[gid])


def expert_gmm_pallas(lhs: jax.Array, rhs: jax.Array, starts: jax.Array,
                      sizes: jax.Array, *, transpose_rhs: bool = False,
                      interpret: bool = False) -> jax.Array:
    """[m, k] x [G, k, n] (or [G, n, k] with ``transpose_rhs``) -> [m, n]
    in ``lhs``'s dtype, f32 accumulation; group ``g`` uses ``rhs[g % G]``.
    Rows in no group are left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    n_rhs = rhs.shape[0]
    tm, tk, tn = row_tile(m), col_tile(k), col_tile(n)
    meta, steps = group_metadata(starts, sizes, m, tm, visit_empty=False)
    n_k = k // tk

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == n_k - 1)
        def _():
            mask = _rows_mask(meta, step, tm, tn)
            out_ref[...] = jnp.where(mask, acc_ref[...],
                                     out_ref[...].astype(jnp.float32)
                                     ).astype(out_ref.dtype)

    def lhs_map(n_i, step, k_i, meta):
        return meta[3][step], k_i

    def rhs_map(n_i, step, k_i, meta):
        g = meta[2][step] % n_rhs
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, step, k_i, meta):
        return meta[3][step], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, steps, n_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",
    )(meta, lhs, rhs)


def expert_tgmm_pallas(lhs: jax.Array, dy: jax.Array, starts: jax.Array,
                       sizes: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """[m, k] x [m, n] -> [groups, k, n]: each group's ``lhs.T @ dy`` over
    its own rows, in ``lhs``'s dtype, f32 accumulation; empty groups give
    zeros."""
    m, k = lhs.shape
    n = dy.shape[1]
    g = sizes.shape[0]
    tm, tk, tn = row_tile(m), col_tile(k), col_tile(n)
    meta, steps = group_metadata(starts, sizes, m, tm, visit_empty=True)

    def kernel(meta, lhs_ref, dy_ref, out_ref, acc_ref):
        step = pl.program_id(2)
        group_ids = meta[2]
        gid = group_ids[step]
        prev = group_ids[jnp.maximum(step - 1, 0)]
        last = step == pl.num_programs(2) - 1
        nxt = group_ids[jnp.where(last, step, step + 1)]

        @pl.when((step == 0) | (prev != gid))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(meta[1][gid] > meta[0][gid])
        def _():
            x = jnp.where(_rows_mask(meta, step, tm, tk),
                          lhs_ref[...].astype(jnp.float32), 0.0)
            d = jnp.where(_rows_mask(meta, step, tm, tn),
                          dy_ref[...].astype(jnp.float32), 0.0)
            acc_ref[...] += jax.lax.dot(
                x.T.astype(lhs_ref.dtype), d.astype(dy_ref.dtype),
                preferred_element_type=jnp.float32)

        @pl.when(last | (nxt != gid))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, step, meta):
        return meta[3][step], k_i

    def dy_map(n_i, k_i, step, meta):
        return meta[3][step], n_i

    def out_map(n_i, k_i, step, meta):
        return meta[2][step], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), dy_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_tgmm",
    )(meta, lhs, dy)
