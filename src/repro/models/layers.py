"""Core layers: norms, MLPs, embeddings, RoPE, losses.

Params are plain nested dicts of jnp arrays (functional style). Layer-stacked
groups carry a leading ``[L, ...]`` axis consumed by ``lax.scan``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ---------------------------------------------------------------- init helpers
def dense_init(key, fan_in: int, shape, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def group_norm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array,
                     n_heads: int, eps: float = 1e-5) -> jax.Array:
    """GroupNorm over each head's channels (RWKV wkv output norm). x: [..., C]."""
    shp = x.shape
    xh = x.reshape(shp[:-1] + (n_heads, shp[-1] // n_heads)).astype(jnp.float32)
    mu = jnp.mean(xh, axis=-1, keepdims=True)
    var = jnp.mean((xh - mu) ** 2, axis=-1, keepdims=True)
    xh = (xh - mu) * jax.lax.rsqrt(var + eps)
    out = xh.reshape(shp) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- matmul
def mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Matmul whose HLO dot emits the input dtype directly.

    For bf16 operands JAX's default keeps an f32 accumulation type on the
    dot, so the SPMD partitioner's partial-sum all-reduce moves f32 — 2× the
    necessary wire bytes on every TP-contracted matmul (w_down, wo, ...).
    preferred_element_type=bf16 makes the all-reduce bf16 (TPU MXU still
    accumulates f32 internally). §Perf iteration 1.
    """
    if a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16:
        return jnp.matmul(a, b, preferred_element_type=jnp.bfloat16)
    return a @ b


# ----------------------------------------------------------------------- mlp
def init_mlp(key, d_model: int, d_ff: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, (d_model, d_ff), dtype),
         "w_down": dense_init(ks[1], d_ff, (d_ff, d_model), dtype)}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[2], d_model, (d_model, d_ff), dtype)
    return p


def apply_mlp(p, x: jax.Array, act: str) -> jax.Array:
    up = x @ p["w_up"]
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    else:
        h = jax.nn.gelu(up)
    return mm(h, p["w_down"])


# ----------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor ``0.1 m ln(s) + 1`` (1 for s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(dim: int, base: float, original_max: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the rotary pairs below ``low`` keep their frequency,
    those from ``high`` on are divided by the factor (YaRN's ramp ends)."""
    def pair(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary inverse frequencies [dim/2] (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): a linear ramp over the pairs
    ``low..high`` blends the plain frequencies into those divided by
    ``factor``."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_correction_range(dim, base, original_max, beta_fast,
                                      beta_slow)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[np.ndarray] = None) -> jax.Array:
    """Half-rotation RoPE. x: [..., S, H, D] or [..., H, D]; positions
    broadcastable to the S axis (or scalar for single-token decode);
    ``inv_freq`` [D/2] in place of the plain ``rope_freqs(D, theta)``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if inv_freq is None else \
        jnp.asarray(inv_freq)                          # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, d/2]
    if x.ndim == angles.ndim + 2:                      # add head axis
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ embedding
def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Token embedding lookup.

    Under a mesh rules env this is a one-hot einsum (bf16) rather than a
    gather: XLA partitions the contraction over the sharded vocab/d dims
    cleanly (FSDP-style weight all-gather), whereas gather-from-sharded-table
    lowers to partial-gather + a full [tokens, d] f32 all-reduce — and its
    *backward* to an even costlier scatter (§Perf iteration 2/3). Single
    device keeps the plain take.
    """
    from repro.dist.sharding import constrain, get_rules
    if get_rules() is None:
        return jnp.take(table, tokens, axis=0)
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
    # align the one-hot's V dim with the table's vocab sharding: the
    # contraction stays shard-local and only [tokens, d] partials reduce
    onehot = constrain(onehot, ("batch",) + (None,) * (onehot.ndim - 2)
                       + ("vocab",))
    return jnp.einsum("...v,vd->...d", onehot, table)


# ----------------------------------------------------------------------- loss
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_logical: int) -> jax.Array:
    """Mean next-token CE, safe for vocab-padded + vocab-sharded logits.

    The one-hot is built from an iota compare (elementwise, fuses shard-local;
    no gather across the sharded vocab axis).
    """
    v_pad = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if v_pad != vocab_logical:
        valid = jnp.arange(v_pad) < vocab_logical
        lf = jnp.where(valid, lf, -1e9)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = labels[..., None] == jnp.arange(v_pad, dtype=labels.dtype)
    label_logit = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    return jnp.mean(lse - label_logit)


def pad_vocab(vocab: int, multiple: int = 512) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple
