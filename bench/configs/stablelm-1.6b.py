"""Plain reference of the stablelm-1.6b configuration as the program runs it.

Decoder-only, pre-norm: RMSNorm, multi-head causal attention with
half-rotation RoPE on every head dimension, a SwiGLU MLP, an untied output
head. Float32, no cache, no chunking, no kernels. Its departures from the
published StableLM-2 (LayerNorm, partial rotary, qkv biases) are the
program's, listed in the configuration file.
"""
import math

import jax
import jax.numpy as jnp

from reference import F32, HIGHEST, cross_entropy, layer_stack, rms_norm, \
    rows


def vocab_padded(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def leaf_specs(m: dict) -> dict:
    """path -> (shape, dtype, init) of every parameter leaf."""
    L, d, ff = m["n_layers"], m["d_model"], m["d_ff"]
    dq, dkv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    w, v = m["dtype"], vocab_padded(m)
    fan = lambda n: ("normal", 1.0 / math.sqrt(n))
    scale = ("uniform", 0.5, 1.5)
    return {
        "embed/w": ((v, d), w, ("normal", 0.02)),
        "final_norm": ((d,), "float32", scale),
        "lm_head/w": ((d, v), w, fan(d)),
        "layers/attn/wq": ((L, d, dq), w, fan(d)),
        "layers/attn/wk": ((L, d, dkv), w, fan(d)),
        "layers/attn/wv": ((L, d, dkv), w, fan(d)),
        "layers/attn/wo": ((L, dq, d), w, fan(dq)),
        "layers/ln1": ((L, d), "float32", scale),
        "layers/ln2": ((L, d), "float32", scale),
        "layers/mlp/w_gate": ((L, d, ff), w, fan(d)),
        "layers/mlp/w_up": ((L, d, ff), w, fan(d)),
        "layers/mlp/w_down": ((L, ff, d), w, fan(ff)),
    }


def flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one token's forward and backward pass: 6 per matmul
    parameter, plus causal attention's scores and weighted sum (4 * seq/2
    * heads * head_dim per layer forward, times 3)."""
    specs = leaf_specs(m)
    matmul = sum(math.prod(s) for k, (s, _, _) in specs.items()
                 if len(s) >= 2 and k not in ("embed/w", "layers/ln1", "layers/ln2"))
    attn = 3 * 4 * (seq + 1) / 2 * m["n_heads"] * m["head_dim"]
    return 6.0 * matmul + m["n_layers"] * attn


def _rope(x, theta):
    """Half-rotation RoPE on [B, T, H, D]."""
    t, dh = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq          # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params, tokens, labels, m: dict, mm):
    """Mean next-token cross entropy of one batch [B, T]."""
    eps, h, hkv, hd = m["norm_eps"], m["n_heads"], m["n_kv_heads"], \
        m["head_dim"]
    x = rows(params["embed/w"], tokens)
    b, t, _ = x.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(x, p):
        y = rms_norm(x, p["ln1"], eps)
        q = mm(y, p["attn/wq"]).reshape(b, t, h, hd)
        k = mm(y, p["attn/wk"]).reshape(b, t, hkv, hd)
        v = mm(y, p["attn/wv"]).reshape(b, t, hkv, hd)
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        k, v = jnp.repeat(k, h // hkv, 2), jnp.repeat(v, h // hkv, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HIGHEST)
        x = x + mm(o.reshape(b, t, h * hd), p["attn/wo"])
        y = rms_norm(x, p["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(y, p["mlp/w_gate"])) * mm(y, p["mlp/w_up"]),
                   p["mlp/w_down"])
        return x, None

    x, _ = jax.lax.scan(block, x, layer_stack(params, "layers/"))
    logits = mm(rms_norm(x, params["final_norm"], eps), params["lm_head/w"])
    return cross_entropy(logits, labels, m["vocab_size"])
