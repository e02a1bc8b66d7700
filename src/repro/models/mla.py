"""Multi-head Latent Attention (DeepSeek-V2/V3). Training uses the expanded
form; decode uses the absorbed form with the compressed latent KV cache —
the whole point of MLA for serving (cache = kv_lora_rank + rope_dim per token).

q comes from a q-LoRA (``wq_a``, ``q_norm``, ``wq_b``), or from one
projection ``wq`` where ``q_lora_rank`` is None (DeepSeek-V2-Lite). The
rope half of q and k is rotated with YaRN's frequencies where
``rope_factor`` > 1, and the softmax scale is then
``qk_head_dim^-0.5 * mscale(factor, mscale_all_dim)^2`` (DeepSeek-V2's
``DeepseekV2YarnRotaryEmbedding`` and ``softmax_scale``). The rotation is
the repository's half-rotation layout: the published checkpoints rotate
interleaved pairs, a fixed permutation of the rope columns of ``wq`` and
``wkv_a``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import attend
from repro.models.layers import (apply_rope, dense_init, rms_norm,
                                 yarn_inv_freq, yarn_mscale)

NEG_INF = -1e9


def rope_inv_freq(m, theta: float):
    """The rope half's inverse frequencies: YaRN's where ``rope_factor`` > 1,
    else None (plain RoPE)."""
    if m.rope_factor <= 1:
        return None
    return yarn_inv_freq(m.qk_rope_head_dim, theta, m.rope_factor,
                         m.rope_original_max, m.beta_fast, m.beta_slow)


def rope_scale(m) -> float:
    """YaRN's factor on cos and sin, ``mscale(s, mscale) / mscale(s,
    mscale_all_dim)``."""
    return yarn_mscale(m.rope_factor, m.mscale) \
        / yarn_mscale(m.rope_factor, m.mscale_all_dim)


def softmax_scale(m) -> float:
    """``qk_head_dim^-0.5``, times ``mscale(s, mscale_all_dim)^2`` under
    YaRN."""
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if m.mscale_all_dim:
        scale *= yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, m, theta):
    out = apply_rope(x, positions, theta, rope_inv_freq(m, theta))
    r = rope_scale(m)
    return out if r == 1.0 else (out * r).astype(out.dtype)


def init_mla(key, d_model: int, n_heads: int, m, dtype):
    ks = jax.random.split(key, 7)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": dense_init(ks[0], d_model, (d_model, n_heads * qk), dtype)}
    else:
        q = {"wq_a": dense_init(ks[0], d_model, (d_model, m.q_lora_rank),
                                dtype),
             "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
             "wq_b": dense_init(ks[1], m.q_lora_rank,
                                (m.q_lora_rank, n_heads * qk), dtype)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], d_model,
                            (d_model, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wk_b": dense_init(ks[3], m.kv_lora_rank,
                           (m.kv_lora_rank, n_heads * m.qk_nope_head_dim), dtype),
        "wv_b": dense_init(ks[4], m.kv_lora_rank,
                           (m.kv_lora_rank, n_heads * m.v_head_dim), dtype),
        "wo": dense_init(ks[5], n_heads * m.v_head_dim,
                         (n_heads * m.v_head_dim, d_model), dtype),
    }


def _project_q(p, x, n_heads, m, theta, positions, eps):
    b = x.shape[0]
    s = x.shape[1] if x.ndim == 3 else 1
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    if "wq" in p:
        q = x @ p["wq"]
    else:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]
    q = q.reshape(b, s, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    return q_nope, _rope(q_rope, positions, m, theta)


def _project_kv_latent(p, x, m, theta, positions, eps):
    ckv = x @ p["wkv_a"]
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = rms_norm(c, p["kv_norm"], eps)
    # shared single-head rope key
    k_rope = _rope(k_rope[..., None, :], positions, m, theta)[..., 0, :]
    return c, k_rope


def apply_mla(p, x: jax.Array, *, n_heads: int, m, theta: float,
              positions, eps: float = 1e-5, chunk: int = 512) -> jax.Array:
    """Training/prefill expanded MLA. x: [B,S,d]."""
    with jax.named_scope("mla.attention"):
        b, s, _ = x.shape
        dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        q_nope, q_rope = _project_q(p, x, n_heads, m, theta, positions, eps)
        c, k_rope = _project_kv_latent(p, x, m, theta, positions, eps)
        k_nope = (c @ p["wk_b"]).reshape(b, s, n_heads, dn)
        v = (c @ p["wv_b"]).reshape(b, s, n_heads, dv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, n_heads, dr))], axis=-1)
        out = attend(q, k, v, causal=True, chunk=chunk,
                     scale=softmax_scale(m))
        return out.reshape(b, s, n_heads * dv) @ p["wo"]


def init_mla_cache(batch: int, seq: int, m, dtype=jnp.bfloat16):
    return {
        "c_kv": jnp.zeros((batch, seq, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq, m.qk_rope_head_dim), dtype),
    }


def decode_mla(p, x: jax.Array, cache, pos, *, n_heads: int, m,
               theta: float, eps: float = 1e-5) -> Tuple[jax.Array, dict]:
    """Absorbed-form one-token decode against the latent cache. x: [B,d]."""
    b, d = x.shape
    dn, dr, dv, dc = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                      m.v_head_dim, m.kv_lora_rank)
    posa = jnp.full((1,), pos)
    q_nope, q_rope = _project_q(p, x[:, None, :], n_heads, m, theta, posa,
                                eps)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]          # [B,H,dn], [B,H,dr]
    c_new, k_rope_new = _project_kv_latent(p, x[:, None, :], m, theta, posa,
                                           eps)
    c_kv = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype), pos, axis=1)
    # absorb W_UK into q: q_c [B,H,dc]
    wk_b = p["wk_b"].reshape(dc, n_heads, dn)
    q_c = jnp.einsum("bhn,chn->bhc", q_nope.astype(jnp.float32),
                     wk_b.astype(jnp.float32))
    scores = (jnp.einsum("bhc,bsc->bhs", q_c, c_kv.astype(jnp.float32))
              + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32)))
    scores *= softmax_scale(m)
    s = c_kv.shape[1]
    valid = jnp.arange(s) <= pos
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx_c = jnp.einsum("bhs,bsc->bhc", w, c_kv.astype(jnp.float32))  # [B,H,dc]
    wv_b = p["wv_b"].reshape(dc, n_heads, dv)
    ctx = jnp.einsum("bhc,chv->bhv", ctx_c, wv_b.astype(jnp.float32))
    out = ctx.reshape(b, n_heads * dv).astype(x.dtype) @ p["wo"]
    return out, {"c_kv": c_kv, "k_rope": k_rope}
