"""Routed MoE without dropped tokens, over the experts held on this chip.

The router scores every token against all ``n_experts`` routed experts
(float32 softmax, greedy top-k; the gates renormalised only where
``norm_topk_prob``, then times ``routed_scaling_factor``). This chip holds
the first ``held`` experts (``MoEConfig.n_held``): a token's routed output
here is ``sum over e in topk(t), e held: g_e(t) * FFN_e(x_t)``. What the
experts held elsewhere add is not computed, and nothing stands in for it.

Dispatch keeps every routed slot: the (token, slot) pairs routed to held
experts are sorted by expert, gathered into rows, and run through the
grouped matmuls (``kernels/expert_gmm``: gate and up as one, then down);
each slot reads its row back, weighted by its gate. The row buffer holds
``tokens * min(top_k, held)`` rows, the most the held experts can
receive, so no capacity ever drops a slot; the kernels, the gather and
its gradient touch only the rows in groups. Shared experts are one dense SwiGLU computed for every token. The
layer holds no cross-token state beyond its input, so it batches under the
FL trainer's ``vmap`` over clients, whose group sizes differ.

The aux load-balance loss follows Switch/GShard.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import expert_matmul
from repro.models.layers import dense_init


def init_moe(key, d_model: int, mo, dtype):
    ks = jax.random.split(key, 8)
    held = mo.held
    p = {
        "router": dense_init(ks[0], d_model, (d_model, mo.n_experts), jnp.float32),
        "w_gate": dense_init(ks[1], d_model, (held, d_model, mo.d_expert), dtype),
        "w_up": dense_init(ks[2], d_model, (held, d_model, mo.d_expert), dtype),
        "w_down": dense_init(ks[3], mo.d_expert, (held, mo.d_expert, d_model), dtype),
    }
    if mo.n_shared:
        ds = (mo.d_shared or mo.d_expert) * mo.n_shared
        p["shared"] = {
            "w_gate": dense_init(ks[4], d_model, (d_model, ds), dtype),
            "w_up": dense_init(ks[5], d_model, (d_model, ds), dtype),
            "w_down": dense_init(ks[6], ds, (ds, d_model), dtype),
        }
    return p


def route(router: jax.Array, xt: jax.Array, mo):
    """xt [T, d] -> (gates [T, k] f32, experts [T, k] i32, aux loss)."""
    logits = jnp.matmul(xt.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                       # [T, E]
    gates, experts = jax.lax.top_k(probs, mo.top_k)               # [T, k]
    if mo.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * mo.routed_scaling_factor
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(experts[:, 0], mo.n_experts,
                                 dtype=jnp.float32), axis=0)
    return gates, experts, mo.n_experts * jnp.sum(me * ce)


def _ffn(rows, p, group_sizes, act: str):
    """The held experts' FFN on rows sorted by expert: SwiGLU's gate and up
    projections as one grouped matmul over their concatenated weights."""
    if act == "swiglu":
        f = p["w_up"].shape[-1]
        gu = expert_matmul(rows, jnp.concatenate([p["w_gate"], p["w_up"]],
                                                 axis=-1), group_sizes)
        h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    else:
        h = jax.nn.gelu(expert_matmul(rows, p["w_up"], group_sizes))
    return expert_matmul(h, p["w_down"], group_sizes)


def apply_moe(p, x: jax.Array, *, mo, act: str = "swiglu"
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    b, s, d = x.shape
    t, k, held = b * s, mo.top_k, mo.held
    xt = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        gates, experts, aux = route(p["router"], xt, mo)

    with jax.named_scope("moe.dispatch"):
        slot_expert = experts.reshape(-1)                         # [T*k]
        # slots routed elsewhere sort last, as group ``held``
        key = jnp.minimum(slot_expert, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                              dtype=jnp.int32)
        n_rows = t * min(k, held)           # the most the held experts get
        rows_cap = -(-n_rows // 8) * 8
        # rows past the last group gather nothing: their source is out of
        # range, so they read zeros and their gradient is dropped
        src = jnp.where(jnp.arange(n_rows) < jnp.sum(group_sizes),
                        order[:n_rows] // k, t)
        src = jnp.pad(src, (0, rows_cap - n_rows), constant_values=t)
        rows = jnp.take(xt, src, axis=0, mode="fill", fill_value=0)  # [R, d]

    with jax.named_scope("moe.experts"):
        y = _ffn(rows, p, group_sizes, act)                       # [R, d]

    with jax.named_scope("moe.combine"):
        # each slot reads its own row back: a held slot its expert's
        # output, any other a row past the last group (zero) or none
        pos = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32)).reshape(t, k)
        w = jnp.where(experts < held, gates, 0.0)
        out = sum(jnp.take(y, pos[:, j], axis=0, mode="fill", fill_value=0)
                  .astype(jnp.float32) * w[:, j, None]
                  for j in range(k)).astype(x.dtype)

    if "shared" in p:
        sh = p["shared"]
        su = xt @ sh["w_up"]
        if act == "swiglu":
            hh = jax.nn.silu(xt @ sh["w_gate"]) * su
        else:
            hh = jax.nn.gelu(su)
        out = out + hh @ sh["w_down"]

    return out.reshape(b, s, d), aux
