"""Pure-jnp oracles for every Pallas kernel (exact semantics incl. ties)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def threshold_find_ref(x2d: jax.Array, ks: jax.Array,
                       e2d: jax.Array | None = None) -> jax.Array:
    """Traced-k thresholds [C, 1] u32: the k-th-largest |.| bit pattern per
    row (of ``e2d + x2d`` when residuals are given), via the 32-halving
    reference bisection."""
    from repro.core.compression import topk_compress_dynamic
    x = x2d.astype(jnp.float32)
    if e2d is not None:
        x = e2d.astype(jnp.float32) + x
    masks = jax.vmap(topk_compress_dynamic)(x, ks.reshape(-1)).mask
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.uint32)
    # the bisection's converged lo == the smallest kept bit pattern
    return jnp.min(jnp.where(masks, bits, jnp.uint32(0xFFFFFFFF)),
                   axis=1, keepdims=True)


def fused_merge_ref(x2d: jax.Array, thresholds: jax.Array, weights: jax.Array,
                    e2d: jax.Array | None = None,
                    active: jax.Array | None = None,
                    *, opwa: bool = False, gamma: float = 1.0, d: int = 1,
                    codec: str = "none", scales: jax.Array | None = None):
    """Oracle for the apply/merge megakernel: same op sequence as the jnp
    route of ``fed.engine.compress_merge_leaf``. ``codec`` + ``scales`` [C, 1]
    mirror the kernel's quantization stage (survivors dequantized before the
    merge; EF absorbs the quantization error). Returns agg [1, n] (plus
    new_residuals [C, n] when ``e2d`` is given)."""
    from repro.core.strategies import CODEC_LEVELS, symmetric_dequantize
    x = x2d.astype(jnp.float32)
    corrected = e2d.astype(jnp.float32) + x if e2d is not None else x
    bits = jax.lax.bitcast_convert_type(jnp.abs(corrected), jnp.uint32)
    mask = bits >= thresholds.reshape(-1, 1)
    vals = jnp.where(mask, corrected, 0.0)
    if codec != "none":
        vals = symmetric_dequantize(vals, scales, CODEC_LEVELS[codec])
    new_res = corrected - vals if e2d is not None else None
    if active is not None:
        act = active.reshape(-1, 1)
        if new_res is not None:
            new_res = jnp.where(act > 0.5, new_res, e2d)
        vals = vals * act.astype(jnp.float32)
        mask = mask & (act > 0.5)
    weighted = jnp.einsum("k,kn->n", weights.reshape(-1).astype(jnp.float32),
                          vals)[None, :]
    if opwa:
        counts = jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)
        m = jnp.where((counts > 0) & (counts <= d), jnp.float32(gamma),
                      jnp.float32(1.0))
        weighted = m * weighted
    return weighted if e2d is None else (weighted, new_res)


def flash_attention_ref(q, k, v, causal: bool = True):
    """[BH, Sq, D] x [BH, Sk, D] -> [BH, Sq, D]; f32 softmax."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)).astype(q.dtype)
