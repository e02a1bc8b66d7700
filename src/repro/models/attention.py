"""Attention: GQA/MHA/MQA with causal / sliding-window / bidirectional / cross
variants, q-chunked (flash-style memory profile) for long sequences, plus
single-token decode against a (possibly sequence-sharded) KV cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import flags
from repro.models.layers import apply_rope, dense_init, mm

NEG_INF = -1e9


# ---------------------------------------------------------------- params
def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype, qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, (d_model, n_heads * head_dim), dtype),
        "wk": dense_init(ks[1], d_model, (d_model, n_kv_heads * head_dim), dtype),
        "wv": dense_init(ks[2], d_model, (d_model, n_kv_heads * head_dim), dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, (n_heads * head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def qkv_proj(p, x: jax.Array, n_heads: int, n_kv_heads: int, head_dim: int):
    """x: [B, S, d] -> q [B,S,H,D], k/v [B,S,Hkv,D]."""
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(b, s, n_heads, head_dim),
            k.reshape(b, s, n_kv_heads, head_dim),
            v.reshape(b, s, n_kv_heads, head_dim))


# ---------------------------------------------------------------- core attend
def _mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """q_pos: [Sq], k_pos: [Sk] -> bool [Sq, Sk] (True = attend)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def _attend_block(q, k, v, mask, scale):
    """q [B,Sq,H,Dqk]; k [B,Sk,Hkv,Dqk]; v [B,Sk,Hkv,Dv] (Dv may differ).

    bf16 inputs feed the MXU directly (f32 scores via preferred accumulation
    — halves the q/k/v HBM traffic vs up-casting; §Perf iteration 1);
    softmax stays f32; probs are cast back to the input dtype for the PV
    matmul (standard flash-attention practice)."""
    b, sq, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    if q.dtype == jnp.bfloat16:
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=jnp.float32) * scale
    else:
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(q.dtype), v,
                     preferred_element_type=q.dtype)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def attend(q, k, v, *, causal: bool = True, window: Optional[int] = None,
           q_offset: int = 0, chunk: int = 512,
           scale: Optional[float] = None) -> jax.Array:
    """Full attention, q-chunked when Sq > chunk to bound score memory.

    q: [B,Sq,H,D]; k,v: [B,Sk,Hkv,D]. FLOP count equals the unmasked product
    (causal masking does not reduce compiled FLOPs — standard for TPU).
    ``scale`` multiplies the scores (default D^-0.5).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sk >= 16384:  # long-context prefill: smaller q-chunks bound the
        chunk = min(chunk, 256)  # [B,H,chunk,Sk] score tiles
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    q_pos_all = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    if sq <= chunk:
        return _attend_block(q, k, v, _mask(q_pos_all, k_pos, causal, window), scale)

    pad = (-sq) % chunk
    if pad:  # non-divisible Sq (e.g. MTP's S-1): pad queries, slice back
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sq_p = sq + pad
    n_chunks = sq_p // chunk
    q_chunks = q.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)

    # checkpoint the chunk body: without it the scan stacks every chunk's
    # [B,H,chunk,Sk] scores/softmax/mask for backward (flash-attention-style
    # memory profile: backward recomputes scores one chunk at a time)
    @jax.checkpoint
    def chunk_attend(qc, kk, vv, idx):
        q_pos = q_offset + idx * chunk + jnp.arange(chunk)
        return _attend_block(qc, kk, vv, _mask(q_pos, k_pos, causal, window),
                             scale)

    def body(_, xs):
        qc, idx = xs
        return None, chunk_attend(qc, k, v, idx)

    _, outs = jax.lax.scan(body, None, (q_chunks, jnp.arange(n_chunks)),
                           unroll=flags.scan_unroll(n_chunks))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq_p, h, v.shape[-1])
    return out[:, :sq] if pad else out


def decode_attend(q, k_cache, v_cache, pos, *, window: Optional[int] = None):
    """Single-token decode. q: [B,H,D]; caches [B,S,Hkv,D]; pos: scalar int.

    Works with a sequence-sharded cache: the softmax reduction over S lowers
    to small per-(B,H) collectives when S is sharded over the model axis.
    """
    b, h, d = q.shape
    s, hkv, dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[-1]
    g = h // hkv
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qg = q.reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    k_pos = jnp.arange(s)
    valid = k_pos <= pos
    if window is not None:
        valid &= k_pos > pos - window
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache.astype(jnp.float32))
    return out.reshape(b, h, dv).astype(q.dtype)


# ---------------------------------------------------------------- module-level
def self_attention(p, x, *, cfg, positions, causal=True, window=None,
                   rope=True, chunk=512):
    """Pre-projected full self-attention for train/prefill. x: [B,S,d].

    q is explicitly head-sharded over the model axis (XLA pads non-divisible
    head counts like qwen's 40/16): without the constraint the partitioner
    splits head_dim instead and every score matmul needs a partial-sum
    all-reduce of the [B,H,Sq,Sk] scores — §Perf iteration 2."""
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = qkv_proj(p, x, h, hkv, hd)
    q = constrain(q, ("batch", None, "heads", None))
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = attend(q, k, v, causal=causal, window=window, chunk=chunk)
    return mm(out.reshape(x.shape[0], x.shape[1], h * hd), p["wo"])


def cross_attention(p, x, memory, *, cfg, chunk=512):
    """x: [B,Sq,d] attends to memory [B,Sk,d]; no mask, no rope."""
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, sq, _ = x.shape
    sk = memory.shape[1]
    q = (x @ p["wq"]).reshape(b, sq, h, hd)
    k = (memory @ p["wk"]).reshape(b, sk, hkv, hd)
    v = (memory @ p["wv"]).reshape(b, sk, hkv, hd)
    out = attend(q, k, v, causal=False, window=None, chunk=chunk)
    return mm(out.reshape(b, sq, h * hd), p["wo"])


def decode_self_attention(p, x, k_cache, v_cache, pos, *, cfg, window=None,
                          rope=True):
    """One-token self-attn with cache update.

    x: [B,d]; caches [B,S,Hkv,D]. Returns (out [B,d], new_k, new_v).
    RoPE is applied at write time for k (absolute positions).
    """
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b = x.shape[0]
    x1 = x[:, None, :]
    q, k, v = qkv_proj(p, x1, h, hkv, hd)
    if rope:
        posa = jnp.full((1,), pos)
        q = apply_rope(q, posa, cfg.rope_theta)
        k = apply_rope(k, posa, cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, axis=1)
    out = decode_attend(q[:, 0], k_cache, v_cache, pos, window=window)
    return out.reshape(b, h * hd) @ p["wo"], k_cache, v_cache


def decode_cross_attention(p, x, ck_cache, cv_cache, *, cfg):
    """One-token cross-attn against precomputed memory KV. x: [B,d]."""
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b = x.shape[0]
    q = (x @ p["wq"]).reshape(b, h, hd)
    s = ck_cache.shape[1]
    out = decode_attend(q, ck_cache, cv_cache, jnp.asarray(s - 1), window=None)
    return out.reshape(b, h * hd) @ p["wo"]
