"""Plain reference of the deepseek-v2-lite configuration as the program runs
it: one chip's share of DeepSeek-V2-Lite.

Decoder-only, pre-norm RMSNorm. Every layer has multi-head latent attention
without a q-LoRA: q = x Wq, the latent c = RMSNorm(x Wkv_a[:512]) expands to
per-head keys and values, and one rope key x Wkv_a[512:] is shared by the
heads. The 64 rope dimensions of q and k are rotated with YaRN's
frequencies (half rotation), and the softmax scale is 192^-0.5 mscale^2.
Layer 0 has a dense SwiGLU MLP. Each later layer has a softmax router over
all 64 routed experts that keeps each token's top 6 gates (not
renormalised, times ``routed_scaling_factor``); of the routed experts only
the ``n_held`` held here are computed, each applied to every token and
weighted by the token's gate for it (zero where the token did not route
to it); two shared experts form one SwiGLU of twice the width. An untied
head over the vocabulary slice. Float32, no sort, kernel, cache or
capacity. The departures from the published model are the program's,
listed in the configuration file.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, HIGHEST, cross_entropy, layer_stack, rms_norm, \
    rows


def vocab_padded(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def _attn_specs(prefix: str, n: int, m: dict, fan, scale) -> dict:
    d, h, a, w = m["d_model"], m["n_heads"], m["mla"], m["dtype"]
    dqk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    r = a["kv_lora_rank"]
    return {
        prefix + "attn/wq": ((n, d, h * dqk), w, fan(d)),
        prefix + "attn/wkv_a": ((n, d, r + a["qk_rope_head_dim"]), w,
                                fan(d)),
        prefix + "attn/kv_norm": ((n, r), "float32", scale),
        prefix + "attn/wk_b": ((n, r, h * a["qk_nope_head_dim"]), w, fan(r)),
        prefix + "attn/wv_b": ((n, r, h * a["v_head_dim"]), w, fan(r)),
        prefix + "attn/wo": ((n, h * a["v_head_dim"], d), w,
                             fan(h * a["v_head_dim"])),
        prefix + "ln1": ((n, d), "float32", scale),
        prefix + "ln2": ((n, d), "float32", scale),
    }


def leaf_specs(m: dict) -> dict:
    """path -> (shape, dtype, init) of every parameter leaf."""
    d, w, v, mo = m["d_model"], m["dtype"], vocab_padded(m), m["moe"]
    nd = mo["first_dense_layers"]
    nm = m["n_layers"] - nd
    ff, fe = m["d_ff"], mo["d_expert"]
    fs = mo["n_shared"] * mo["d_shared"]
    held = mo["n_held"] or mo["n_experts"]
    fan = lambda n: ("normal", 1.0 / math.sqrt(n))
    scale = ("uniform", 0.5, 1.5)
    specs = {
        "embed/w": ((v, d), w, ("normal", 0.02)),
        "final_norm": ((d,), "float32", scale),
        "lm_head/w": ((d, v), w, fan(d)),
        **_attn_specs("dense_layers/", nd, m, fan, scale),
        "dense_layers/mlp/w_gate": ((nd, d, ff), w, fan(d)),
        "dense_layers/mlp/w_up": ((nd, d, ff), w, fan(d)),
        "dense_layers/mlp/w_down": ((nd, ff, d), w, fan(ff)),
    }
    if nm <= 0:             # as deep as the dense layers: no MoE layer
        return specs
    return {
        **specs,
        **_attn_specs("moe_layers/", nm, m, fan, scale),
        "moe_layers/moe/router": ((nm, d, mo["n_experts"]), "float32",
                                  fan(d)),
        "moe_layers/moe/w_gate": ((nm, held, d, fe), w, fan(d)),
        "moe_layers/moe/w_up": ((nm, held, d, fe), w, fan(d)),
        "moe_layers/moe/w_down": ((nm, held, fe, d), w, fan(fe)),
        "moe_layers/moe/shared/w_gate": ((nm, d, fs), w, fan(d)),
        "moe_layers/moe/shared/w_up": ((nm, d, fs), w, fan(d)),
        "moe_layers/moe/shared/w_down": ((nm, fs, d), w, fan(fs)),
    }


def _active_matmul_params(m: dict) -> float:
    """Matmul parameters one token passes through: every matrix but the
    embedding and the routed experts, plus the routed experts at their
    expected share here (top_k * held / n_experts experts of each layer)."""
    mo = m["moe"]
    n = 0.0
    for k, (s, _, _) in leaf_specs(m).items():
        if len(s) < 2 or k == "embed/w" or k.endswith("ln1") \
                or k.endswith("ln2") or k.endswith("kv_norm"):
            continue
        size = math.prod(s)
        if k in ("moe_layers/moe/w_gate", "moe_layers/moe/w_up",
                 "moe_layers/moe/w_down"):
            size *= mo["top_k"] / mo["n_experts"]       # of held experts
        n += size
    return n


def flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one token's forward and backward pass: 6 per active
    matmul parameter, plus causal attention's scores (q.k over 192
    dimensions) and weighted sum (over 128), 2 * seq/2 * heads each per
    layer forward, times 3."""
    a = m["mla"]
    dqk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    attn = 3 * 2 * (dqk + a["v_head_dim"]) * (seq + 1) / 2 * m["n_heads"]
    return 6.0 * _active_matmul_params(m) + m["n_layers"] * attn


def expert_gmm_work(m: dict, mix: dict) -> dict:
    """One round's grouped expert products at the expected routed rows
    (every client's tokens times top_k * held / n_experts, per MoE layer):
    the 9 products of a local step (gate, up and down forward; the rows'
    and the weights' gradient of each backward), each reading its two
    operands and writing its result once in bfloat16. Recomputation under
    remat is not counted. Returns {rows, flops, bytes} per round."""
    mo = m["moe"]
    held = mo["n_held"] or mo["n_experts"]
    d, f = m["d_model"], mo["d_expert"]
    layers = m["n_layers"] - mo["first_dense_layers"]
    steps = mix["clients"] * mix["local_steps"]
    r = mix["batch"] * mix["seq"] * mo["top_k"] * held / mo["n_experts"]
    size = 2                                            # bfloat16
    per_product_flops = 2.0 * r * d * f
    per_product_bytes = size * (r * d + r * f + held * d * f)
    return {"rows": steps * layers * r,
            "flops": 9 * steps * layers * per_product_flops,
            "bytes": 9 * steps * layers * per_product_bytes}


def yarn_inv_freq(dim: int, base: float, a: dict) -> np.ndarray:
    """YaRN's rotary inverse frequencies [dim/2], as DeepSeek-V2 computes
    them (float64 on the host)."""
    factor, L0 = a["rope_factor"], a["rope_original_max"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    if factor <= 1:
        return extra.astype(np.float32)

    def pair(rotations):
        return dim * math.log(L0 / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(pair(a["beta_fast"])), 0)
    high = min(math.ceil(pair(a["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def mscale(s: float, mult: float) -> float:
    return 1.0 if s <= 1 else 0.1 * mult * math.log(s) + 1.0


def softmax_scale(a: dict) -> float:
    s = (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]) ** -0.5
    if a["mscale_all_dim"]:
        s *= mscale(a["rope_factor"], a["mscale_all_dim"]) ** 2
    return s


def _rope(x, inv_freq, attn_factor):
    """Half-rotation RoPE on [B, T, H, D] with the given frequencies; cos
    and sin times ``attn_factor``."""
    t, dh = x.shape[1], x.shape[-1]
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * attn_factor)[:, None]
    sin = (jnp.sin(ang) * attn_factor)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(y, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)


def _top_k_mask(probs, k: int):
    """[T, E] bool: each token's k largest probabilities, the lower expert
    index first among equal ones (a token's rank is the number of experts
    that come before it)."""
    e = probs.shape[-1]
    idx = jnp.arange(e)
    before = (probs[:, None, :] > probs[:, :, None]) | (
        (probs[:, None, :] == probs[:, :, None])
        & (idx[None, None, :] < idx[None, :, None]))
    return jnp.sum(before, -1) < k


def moe_ffn(y, p, mo: dict, mm):
    """One MoE layer's FFN on the normed tokens y [N, d]: the held routed
    experts' gated outputs plus the shared experts'. ``p`` holds the
    layer's ``moe/...`` leaves."""
    held = mo["n_held"] or mo["n_experts"]
    probs = jax.nn.softmax(mm(y, p["moe/router"]), -1)           # [N, E]
    chosen = _top_k_mask(probs, mo["top_k"])
    gates = jnp.where(chosen, probs, 0.0)
    if mo["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * mo["routed_scaling_factor"]

    def expert(acc, e):
        """Held expert e on every token, weighted by its gates."""
        out = _swiglu(y, p["moe/w_gate"][e], p["moe/w_up"][e],
                      p["moe/w_down"][e], mm)
        return acc + gates[:, e, None] * out, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(y), jnp.arange(held))
    return routed + _swiglu(y, p["moe/shared/w_gate"], p["moe/shared/w_up"],
                            p["moe/shared/w_down"], mm)


def loss(params, tokens, labels, m: dict, mm):
    """Mean next-token cross entropy of one batch [B, T]."""
    eps, h, a, mo = m["norm_eps"], m["n_heads"], m["mla"], m["moe"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    r = a["kv_lora_rank"]
    inv_freq = yarn_inv_freq(dr, m["rope_theta"], a)
    attn_factor = mscale(a["rope_factor"], a["mscale"]) \
        / mscale(a["rope_factor"], a["mscale_all_dim"])
    scale = softmax_scale(a)
    x = rows(params["embed/w"], tokens)
    b, t, _ = x.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def attention(x, p):
        y = rms_norm(x, p["ln1"], eps)
        q = mm(y, p["attn/wq"]).reshape(b, t, h, dn + dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], inv_freq, attn_factor)
        ckv = mm(y, p["attn/wkv_a"])
        c = rms_norm(ckv[..., :r], p["attn/kv_norm"], eps)
        k_pe = _rope(ckv[..., None, r:], inv_freq, attn_factor)  # one head
        k_nope = mm(c, p["attn/wk_b"]).reshape(b, t, h, dn)
        v = mm(c, p["attn/wv_b"]).reshape(b, t, h, dv)
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=HIGHEST)
             + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0],
                          precision=HIGHEST)) * scale
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
        return x + mm(o.reshape(b, t, h * dv), p["attn/wo"])

    @jax.checkpoint
    def dense_block(x, p):
        x = attention(x, p)
        y = rms_norm(x, p["ln2"], eps)
        return x + _swiglu(y, p["mlp/w_gate"], p["mlp/w_up"],
                           p["mlp/w_down"], mm), None

    @jax.checkpoint
    def moe_block(x, p):
        x = attention(x, p)
        y = rms_norm(x, p["ln2"], eps).reshape(b * t, -1)
        return x + moe_ffn(y, p, mo, mm).reshape(b, t, -1), None

    x, _ = jax.lax.scan(dense_block, x, layer_stack(params, "dense_layers/"))
    if m["n_layers"] > mo["first_dense_layers"]:
        x, _ = jax.lax.scan(moe_block, x, layer_stack(params, "moe_layers/"))
    logits = mm(rms_norm(x, params["final_norm"], eps), params["lm_head/w"])
    return cross_entropy(logits, labels, m["vocab_size"])
