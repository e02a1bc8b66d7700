"""Pallas TPU kernel: causal flash attention (forward, online softmax).

The serving/prefill hot path: q tiles stay in VMEM while K/V stream through
in blk_k-sized blocks with running (max, denominator, accumulator) — one
HBM pass over K/V per q tile, no [Sq, Sk] score materialization. f32
accumulation regardless of input dtype (MXU-style).

Layout: heads are flattened into the grid's first axis; grid =
(B*H, Sq/blk_q). The pure-jnp oracle is ref.flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(blk_k: int, scale: float, causal: bool, blk_q: int,
                  q_ref, k_ref, v_ref, o_ref):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [blk_q, D]
    sk = k_ref.shape[1]
    d = q.shape[-1]
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(i * blk_k, blk_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(i * blk_k, blk_k), :].astype(jnp.float32)
        s = q @ kb.T                                   # [blk_q, blk_k]
        if causal:
            k_pos = i * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ vb
        return m_new, l_new, acc_new

    m0 = jnp.full((blk_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q,), jnp.float32)
    acc0 = jnp.zeros((blk_q, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, sk // blk_k, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True, blk_q: int = 128,
                           blk_k: int = 128, interpret: bool = True
                           ) -> jax.Array:
    """q: [BH, Sq, D]; k, v: [BH, Sk, D] (heads pre-flattened).

    Sq % blk_q == 0 and Sk % blk_k == 0 (pad in ops.py)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % blk_q == 0 and sk % blk_k == 0
    scale = 1.0 / (d ** 0.5)
    grid = (bh, sq // blk_q)
    return pl.pallas_call(
        functools.partial(_flash_kernel, blk_k, scale, causal, blk_q),
        grid=grid,
        in_specs=[pl.BlockSpec((1, blk_q, d), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
