"""Model assembly for all assigned architecture families.

Functional style: ``Model(cfg)`` exposes ``init`` / ``loss_fn`` / ``prefill`` /
``decode_step`` / ``init_cache``. Layer stacks carry a leading ``[L, ...]``
axis and run under ``lax.scan`` (compact HLO, bounded compile time at 61+
layers), with ``jax.checkpoint`` remat for training.

Families: dense (stablelm/yi/qwen), moe (+MLA for deepseek; +MTP), hybrid
(hymba: parallel GQA-SWA + SSD branches), ssm (rwkv6), encdec (whisper),
vlm (llama-3.2-vision: 4-self + 1-cross supergroups).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.models import flags
from repro.models import attention as attn
from repro.models import mamba, mla, moe, rwkv6
from repro.models.layers import (apply_mlp, cross_entropy, dense_init,
                                 embed_init, embed_lookup, init_mlp,
                                 layer_norm, pad_vocab, rms_norm, _dtype)

Params = Dict[str, Any]


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def sinusoidal_pos(seq: int, d: int, offset=0) -> jax.Array:
    pos = offset + jnp.arange(seq)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = _dtype(cfg.dtype)
        self.v_pad = pad_vocab(cfg.vocab_size, 256)

    # =================================================================== init
    def init(self, key) -> Params:
        cfg = self.cfg
        d = cfg.d_model
        keys = iter(jax.random.split(key, 64))
        p: Params = {
            "embed": {"w": embed_init(next(keys), (self.v_pad, d), self.dtype)},
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": {"w": dense_init(next(keys), d, (d, self.v_pad), self.dtype)},
        }
        if cfg.family == "ssm":
            p["ln0_s"] = jnp.ones((d,), jnp.float32)
            p["ln0_b"] = jnp.zeros((d,), jnp.float32)
            p["final_norm_b"] = jnp.zeros((d,), jnp.float32)
            p["layers"] = self._init_stack(next(keys), cfg.n_layers, self._init_rwkv_block)
        elif cfg.family == "encdec":
            p["encoder"] = {
                "layers": self._init_stack(next(keys), cfg.encdec.n_enc_layers,
                                           self._init_dense_block),
                "final_norm": jnp.ones((d,), jnp.float32),
            }
            p["layers"] = self._init_stack(next(keys), cfg.n_layers,
                                           self._init_encdec_block)
        elif cfg.family == "vlm":
            v = cfg.vision
            n_groups = v.n_cross_layers
            per = cfg.n_layers // n_groups
            p["vis_proj"] = dense_init(next(keys), v.d_vision, (v.d_vision, d), self.dtype)
            p["groups"] = {
                "self": self._init_stack(next(keys), n_groups * per,
                                         self._init_dense_block,
                                         reshape=(n_groups, per)),
                "cross": self._init_stack(next(keys), n_groups, self._init_cross_block),
            }
        elif cfg.family == "moe":
            nd = cfg.moe.first_dense_layers
            if nd:
                p["dense_layers"] = self._init_stack(next(keys), nd, self._init_dense_block)
            if cfg.n_layers > nd:
                p["moe_layers"] = self._init_stack(
                    next(keys), cfg.n_layers - nd, self._init_moe_block)
            if cfg.mtp_depth:
                p["mtp"] = {
                    "proj": dense_init(next(keys), 2 * d, (2 * d, d), self.dtype),
                    "norm_h": jnp.ones((d,), jnp.float32),
                    "norm_e": jnp.ones((d,), jnp.float32),
                    "block": self._init_dense_block(next(keys)),
                }
        else:  # dense / hybrid
            p["layers"] = self._init_stack(next(keys), cfg.n_layers,
                                           self._init_block)
        return p

    def _init_stack(self, key, n, init_one, reshape=None):
        ks = jax.random.split(key, n)
        stacked = jax.vmap(init_one)(ks)
        if reshape is not None:
            stacked = jax.tree.map(
                lambda x: x.reshape(reshape + x.shape[1:]), stacked)
        return stacked

    def _init_attn(self, key):
        cfg = self.cfg
        if cfg.mla is not None:
            return mla.init_mla(key, cfg.d_model, cfg.n_heads, cfg.mla, self.dtype)
        return attn.init_attention(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.resolved_head_dim, self.dtype, cfg.qkv_bias)

    def _init_dense_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {"attn": self._init_attn(k1),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, self.dtype),
                "ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32)}

    def _init_block(self, key):
        cfg = self.cfg
        p = self._init_dense_block(key)
        if cfg.ssm is not None:  # hymba hybrid: parallel SSM branch
            k = jax.random.fold_in(key, 7)
            p["ssm"] = mamba.init_ssm(k, cfg.d_model, cfg.ssm, self.dtype)
            p["attn_out_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
            p["ssm_out_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        return p

    def _init_moe_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {"attn": self._init_attn(k1),
                "moe": moe.init_moe(k2, cfg.d_model, cfg.moe, self.dtype),
                "ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32)}

    def _init_rwkv_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {"tm": rwkv6.init_time_mix(k1, cfg.d_model, cfg.rwkv, self.dtype),
                "cm": rwkv6.init_channel_mix(k2, cfg.d_model, cfg.d_ff, self.dtype),
                "ln1_s": jnp.ones((cfg.d_model,), jnp.float32),
                "ln1_b": jnp.zeros((cfg.d_model,), jnp.float32),
                "ln2_s": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2_b": jnp.zeros((cfg.d_model,), jnp.float32)}

    def _init_cross_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {"xattn": attn.init_attention(k1, cfg.d_model, cfg.n_heads,
                                             cfg.n_kv_heads, cfg.resolved_head_dim,
                                             self.dtype),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, self.dtype),
                "gate_attn": jnp.zeros((), jnp.float32),
                "gate_mlp": jnp.zeros((), jnp.float32),
                "ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32)}

    def _init_encdec_block(self, key):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        return {"attn": self._init_attn(k1),
                "xattn": attn.init_attention(k2, cfg.d_model, cfg.n_heads,
                                             cfg.n_kv_heads, cfg.resolved_head_dim,
                                             self.dtype),
                "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act, self.dtype),
                "ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "lnx": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32)}

    # ============================================================ train blocks
    def _window_flags(self):
        """Per-layer effective window (int32; S+1 => effectively global)."""
        cfg = self.cfg
        if cfg.window is None:
            return None
        w = jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
        for g in cfg.global_layers:
            w = w.at[g].set(jnp.iinfo(jnp.int32).max // 2)
        return w

    def _block_fwd(self, p, x, positions, window, chunk=512):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.mla is not None:
            a = mla.apply_mla(p["attn"], h, n_heads=cfg.n_heads, m=cfg.mla,
                              theta=cfg.rope_theta, positions=positions,
                              eps=cfg.norm_eps, chunk=chunk)
        else:
            a = attn.self_attention(p["attn"], h, cfg=cfg, positions=positions,
                                    causal=True, window=window, chunk=chunk)
        if cfg.ssm is not None:
            s = mamba.apply_ssm(p["ssm"], h, d_model=cfg.d_model, ssm_cfg=cfg.ssm)
            mix = 0.5 * (rms_norm(a, p["attn_out_norm"], cfg.norm_eps)
                         + rms_norm(s, p["ssm_out_norm"], cfg.norm_eps))
            x = x + mix
        else:
            x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            mo_out, aux = moe.apply_moe(p["moe"], h2, mo=cfg.moe, act=cfg.act)
            # carry constraint: the layer-scan's saved activation stack is
            # d_model-sharded for FSDP archs (sequence-parallel style)
            return constrain(x + mo_out, ("batch", None, "act_d")), aux
        out = constrain(x + apply_mlp(p["mlp"], h2, cfg.act),
                        ("batch", None, "act_d"))
        return out, jnp.float32(0.0)

    def _rwkv_block_fwd(self, p, x):
        cfg = self.cfg
        h = layer_norm(x, p["ln1_s"], p["ln1_b"], cfg.norm_eps)
        x = x + rwkv6.apply_time_mix(p["tm"], h, n_heads=cfg.n_heads,
                                     rwkv_cfg=cfg.rwkv)
        h = layer_norm(x, p["ln2_s"], p["ln2_b"], cfg.norm_eps)
        return x + rwkv6.apply_channel_mix(p["cm"], h)

    def _cross_block_fwd(self, p, x, memory):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        g_a = jnp.tanh(p["gate_attn"]).astype(x.dtype)
        x = x + g_a * attn.cross_attention(p["xattn"], h, memory, cfg=cfg)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        g_m = jnp.tanh(p["gate_mlp"]).astype(x.dtype)
        return x + g_m * apply_mlp(p["mlp"], h, cfg.act)

    def _encdec_block_fwd(self, p, x, memory, positions):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + attn.self_attention(p["attn"], h, cfg=cfg, positions=positions,
                                    causal=True, rope=False)
        h = rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn.cross_attention(p["xattn"], h, memory, cfg=cfg)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + apply_mlp(p["mlp"], h, cfg.act)

    # ============================================================== forward
    def _backbone(self, params, x, positions) -> Tuple[jax.Array, jax.Array]:
        """Token embeddings -> final hidden states. Returns (h, aux_loss)."""
        cfg = self.cfg
        remat = cfg.remat
        aux0 = jnp.float32(0.0)
        if cfg.family == "ssm":
            x = layer_norm(x, params["ln0_s"], params["ln0_b"], cfg.norm_eps)
            body = _remat(lambda h, p: self._rwkv_block_fwd(p, h), remat)
            x, _ = jax.lax.scan(lambda h, p: (body(h, p), None), x,
                                params["layers"],
                                unroll=flags.scan_unroll(cfg.n_layers))
            return x, aux0
        if cfg.family == "moe":
            if "dense_layers" in params:
                # leading dense layers (<=3): unrolled python loop so HLO
                # cost analysis counts them exactly (scan bodies count once)
                body = _remat(lambda h, p: self._block_fwd(
                    p, h, positions, None)[0], remat)
                nd = cfg.moe.first_dense_layers
                for i in range(nd):
                    x = body(x, jax.tree.map(lambda a: a[i],
                                             params["dense_layers"]))
            if "moe_layers" not in params:
                return x, aux0
            body2 = _remat(lambda h, p: self._block_fwd(p, h, positions, None), remat)

            def moe_step(carry, p):
                h, aux = carry
                h, a = body2(h, p)
                return (h, aux + a), None

            n_moe = cfg.n_layers - cfg.moe.first_dense_layers
            (x, aux), _ = jax.lax.scan(moe_step, (x, aux0),
                                       params["moe_layers"],
                                       unroll=flags.scan_unroll(n_moe))
            return x, aux
        if cfg.family == "vlm":
            raise RuntimeError("vlm uses _backbone_vlm")
        # dense / hybrid
        wins = self._window_flags()

        def step(h, xs):
            if wins is None:
                p = xs
                return _remat(lambda hh, pp: self._block_fwd(
                    pp, hh, positions, None)[0], remat)(h, p), None
            p, w = xs
            return _remat(lambda hh, pw: self._block_fwd(
                pw[0], hh, positions, pw[1])[0], remat)(h, (p, w)), None

        xs = params["layers"] if wins is None else (params["layers"], wins)
        x, _ = jax.lax.scan(step, x, xs,
                            unroll=flags.scan_unroll(cfg.n_layers))
        return x, aux0

    def _backbone_vlm(self, params, x, vis, positions):
        cfg = self.cfg
        remat = cfg.remat
        self_body = _remat(lambda h, p: self._block_fwd(
            p, h, positions, None)[0], remat)
        cross_body = _remat(lambda h, p: self._cross_block_fwd(p, h, vis), remat)

        per = cfg.n_layers // cfg.vision.n_cross_layers

        def group(h, gp):
            h, _ = jax.lax.scan(lambda hh, p: (self_body(hh, p), None),
                                h, gp["self"], unroll=flags.scan_unroll(per))
            h = cross_body(h, gp["cross"])
            return h, None

        x, _ = jax.lax.scan(group, x, params["groups"],
                            unroll=flags.scan_unroll(cfg.vision.n_cross_layers))
        return x, jnp.float32(0.0)

    def _encode(self, params, frames):
        """Whisper encoder over precomputed frame embeddings [B,S,d]."""
        cfg = self.cfg
        b, s, d = frames.shape
        x = frames.astype(self.dtype) + sinusoidal_pos(s, d).astype(self.dtype)
        positions = jnp.arange(s)

        def enc_step(h, p):  # bidirectional: causal=False via direct call
            hh = rms_norm(h, p["ln1"], cfg.norm_eps)
            a = attn.self_attention(p["attn"], hh, cfg=cfg, positions=positions,
                                    causal=False, rope=False)
            h = h + a
            hh = rms_norm(h, p["ln2"], cfg.norm_eps)
            return h + apply_mlp(p["mlp"], hh, cfg.act)

        enc_body = _remat(enc_step, cfg.remat)
        x, _ = jax.lax.scan(lambda h, p: (enc_body(h, p), None),
                            x, params["encoder"]["layers"],
                            unroll=flags.scan_unroll(cfg.encdec.n_enc_layers))
        return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    # ================================================================= losses
    def loss_fn(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"]["w"], tokens)
        x = constrain(x, ("batch", "seq", "embed"))
        positions = jnp.arange(s)
        aux = jnp.float32(0.0)
        if cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
            dec_pos = sinusoidal_pos(s, cfg.d_model).astype(self.dtype)
            x = x + dec_pos
            body = _remat(lambda h, p: self._encdec_block_fwd(
                p, h, memory, positions), cfg.remat)
            x, _ = jax.lax.scan(lambda h, p: (body(h, p), None), x,
                                params["layers"],
                                unroll=flags.scan_unroll(cfg.n_layers))
        elif cfg.family == "vlm":
            vis = batch["patches"].astype(self.dtype) @ params["vis_proj"]
            x, aux = self._backbone_vlm(params, x, vis, positions)
        else:
            x, aux = self._backbone(params, x, positions)
        x = constrain(x, ("batch", "seq", "embed"))
        h_final = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = h_final @ params["lm_head"]["w"]
        logits = constrain(logits, ("batch", "seq", "vocab"))
        ce = cross_entropy(logits, labels, cfg.vocab_size)
        metrics = {"ce": ce}
        loss = ce
        if cfg.moe is not None:
            loss = loss + cfg.moe.aux_loss_weight * aux
            metrics["aux"] = aux
        if cfg.mtp_depth and "mtp" in params:
            mtp_loss = self._mtp_loss(params, h_final, tokens, labels, positions)
            loss = loss + 0.3 * mtp_loss
            metrics["mtp"] = mtp_loss
        return loss, metrics

    def _mtp_loss(self, params, h, tokens, labels, positions):
        """DeepSeek MTP: predict t+2 from [h_t ; emb(token_{t+1})]."""
        cfg = self.cfg
        mp = params["mtp"]
        emb_next = embed_lookup(params["embed"]["w"], tokens[:, 1:])
        h_in = jnp.concatenate(
            [rms_norm(h[:, :-1], mp["norm_h"], cfg.norm_eps),
             rms_norm(emb_next, mp["norm_e"], cfg.norm_eps)], axis=-1)
        x = h_in @ mp["proj"]
        x, _ = self._block_fwd(mp["block"], x, positions[:-1], None)
        logits = rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]["w"]
        # labels shifted by one more step: logits[t] predicts labels[t+1]
        return cross_entropy(logits[:, :-1], labels[:, 2:], cfg.vocab_size)

    # ================================================================ caches
    def init_cache(self, batch: int, seq: int, dtype=jnp.bfloat16) -> Params:
        cfg = self.cfg
        hkv, hd, L = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_layers
        if cfg.family == "ssm":
            one = rwkv6.init_rwkv_cache(batch, cfg.d_model, cfg.n_heads, cfg.rwkv)
            return jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), one)
        if cfg.mla is not None:
            nd = cfg.moe.first_dense_layers if cfg.moe else 0
            cache = {"mla": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (L,) + x.shape),
                mla.init_mla_cache(batch, seq, cfg.mla, dtype))}
            # dense leading layers still use MLA attention in our impl, so the
            # cache is uniform across all layers.
            return cache
        kv = {"k": jnp.zeros((L, batch, seq, hkv, hd), dtype),
              "v": jnp.zeros((L, batch, seq, hkv, hd), dtype)}
        if cfg.family == "hybrid":
            one = mamba.init_ssm_cache(batch, cfg.d_model, cfg.ssm)
            kv["ssm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (L,) + x.shape), one)
        if cfg.family == "encdec":
            kv["ck"] = jnp.zeros((L, batch, seq, hkv, hd), dtype)
            kv["cv"] = jnp.zeros((L, batch, seq, hkv, hd), dtype)
        if cfg.family == "vlm":
            v = cfg.vision
            g, per = v.n_cross_layers, cfg.n_layers // v.n_cross_layers
            kv = {"k": jnp.zeros((g, per, batch, seq, hkv, hd), dtype),
                  "v": jnp.zeros((g, per, batch, seq, hkv, hd), dtype),
                  "ck": jnp.zeros((g, batch, v.n_patches, hkv, hd), dtype),
                  "cv": jnp.zeros((g, batch, v.n_patches, hkv, hd), dtype)}
        return kv

    # ================================================================= decode
    def decode_step(self, params, cache, tokens, pos
                    ) -> Tuple[jax.Array, Params]:
        """One-token decode. tokens: [B] int32; pos: scalar int32."""
        cfg = self.cfg
        x = embed_lookup(params["embed"]["w"], tokens)       # [B, d]
        if cfg.family == "ssm":
            x = layer_norm(x, params["ln0_s"], params["ln0_b"], cfg.norm_eps)
            x, new_cache = self._decode_rwkv(params, cache, x)
        elif cfg.mla is not None:
            x, new_cache = self._decode_mla(params, cache, x, pos)
        elif cfg.family == "vlm":
            x, new_cache = self._decode_vlm(params, cache, x, pos)
        elif cfg.family == "encdec":
            x = x + sinusoidal_pos(1, cfg.d_model, offset=pos)[0].astype(x.dtype)
            x, new_cache = self._decode_encdec(params, cache, x, pos)
        else:
            x, new_cache = self._decode_dense(params, cache, x, pos)
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = h @ params["lm_head"]["w"]
        logits = constrain(logits, ("batch", "vocab"))
        return logits, new_cache

    def _decode_block(self, p, x, kc, vc, pos, window, ssm_cache=None):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        a, kc, vc = attn.decode_self_attention(p["attn"], h, kc, vc, pos,
                                               cfg=cfg, window=window)
        new_ssm = None
        if ssm_cache is not None:
            s, new_ssm = mamba.decode_ssm(p["ssm"], h, ssm_cache,
                                          d_model=cfg.d_model, ssm_cfg=cfg.ssm)
            x = x + 0.5 * (rms_norm(a, p["attn_out_norm"], cfg.norm_eps)
                           + rms_norm(s, p["ssm_out_norm"], cfg.norm_eps))
        else:
            x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            mo_out, _ = moe.apply_moe(p["moe"], h2[:, None, :], mo=cfg.moe,
                                      act=cfg.act)
            x = x + mo_out[:, 0]
        else:
            x = x + apply_mlp(p["mlp"], h2, cfg.act)
        return x, kc, vc, new_ssm

    def _decode_dense(self, params, cache, x, pos):
        cfg = self.cfg
        if cfg.family == "moe":  # GQA MoE (kimi): split dense/moe layer groups
            return self._decode_moe_gqa(params, cache, x, pos)
        wins = self._window_flags()
        hybrid = cfg.family == "hybrid"

        def body(h, xs):
            if hybrid:
                p, kc, vc, sc, w = xs
                h, kc, vc, sc = self._decode_block(p, h, kc, vc, pos, w, sc)
                return h, (kc, vc, sc)
            if wins is not None:
                p, kc, vc, w = xs
                h, kc, vc, _ = self._decode_block(p, h, kc, vc, pos, w)
                return h, (kc, vc)
            p, kc, vc = xs
            h, kc, vc, _ = self._decode_block(p, h, kc, vc, pos, None)
            return h, (kc, vc)

        unr = flags.scan_unroll(cfg.n_layers)
        if hybrid:
            xs = (params["layers"], cache["k"], cache["v"], cache["ssm"], wins)
            x, (k, v, sc) = jax.lax.scan(body, x, xs, unroll=unr)
            return x, {"k": k, "v": v, "ssm": sc}
        if wins is not None:
            xs = (params["layers"], cache["k"], cache["v"], wins)
            x, (k, v) = jax.lax.scan(body, x, xs, unroll=unr)
            return x, {"k": k, "v": v}
        xs = (params["layers"], cache["k"], cache["v"])
        x, (k, v) = jax.lax.scan(body, x, xs, unroll=unr)
        return x, {"k": k, "v": v}

    def _decode_moe_gqa(self, params, cache, x, pos):
        cfg = self.cfg
        nd = cfg.moe.first_dense_layers

        def body_dense(h, xs):
            p, kc, vc = xs
            h, kc, vc, _ = self._decode_block(p, h, kc, vc, pos, None)
            return h, (kc, vc)

        def body_moe(h, xs):
            p, kc, vc = xs
            h, kc, vc, _ = self._decode_block(p, h, kc, vc, pos, None)
            return h, (kc, vc)

        ks, vs = cache["k"], cache["v"]
        if nd and "dense_layers" in params:
            kds, vds = [], []
            for i in range(nd):  # unrolled (see _backbone)
                p_i = jax.tree.map(lambda a: a[i], params["dense_layers"])
                x, (kd, vd) = body_dense(x, (p_i, ks[i], vs[i]))
                kds.append(kd)
                vds.append(vd)
        x, (km, vm) = jax.lax.scan(
            body_moe, x, (params["moe_layers"], ks[nd:], vs[nd:]),
            unroll=flags.scan_unroll(cfg.n_layers - nd))
        if nd and "dense_layers" in params:
            k = jnp.concatenate([jnp.stack(kds), km], axis=0)
            v = jnp.concatenate([jnp.stack(vds), vm], axis=0)
        else:
            k, v = km, vm
        return x, {"k": k, "v": v}

    def _decode_mla(self, params, cache, x, pos):
        cfg = self.cfg
        nd = cfg.moe.first_dense_layers if cfg.moe else 0

        def make_body(use_moe):
            def body(h, xs):
                p, c = xs
                hh = rms_norm(h, p["ln1"], cfg.norm_eps)
                a, c = mla.decode_mla(p["attn"], hh, c, pos, n_heads=cfg.n_heads,
                                      m=cfg.mla, theta=cfg.rope_theta,
                                      eps=cfg.norm_eps)
                h = h + a
                h2 = rms_norm(h, p["ln2"], cfg.norm_eps)
                if use_moe:
                    mo_out, _ = moe.apply_moe(p["moe"], h2[:, None, :],
                                              mo=cfg.moe, act=cfg.act)
                    h = h + mo_out[:, 0]
                else:
                    h = h + apply_mlp(p["mlp"], h2, cfg.act)
                return h, c
            return body

        mc = cache["mla"]
        sub = lambda lo, hi: jax.tree.map(lambda a: a[lo:hi], mc)
        outs = []
        if nd and "dense_layers" in params:
            body_d = make_body(False)
            cs = []
            for i in range(nd):  # unrolled (see _backbone)
                p_i = jax.tree.map(lambda a: a[i], params["dense_layers"])
                c_i = jax.tree.map(lambda a: a[i], mc)
                x, c_i = body_d(x, (p_i, c_i))
                cs.append(c_i)
            outs.append(jax.tree.map(lambda *xs: jnp.stack(xs), *cs))
        x, c2 = jax.lax.scan(make_body(True), x,
                             (params["moe_layers"], sub(nd, cfg.n_layers)),
                             unroll=flags.scan_unroll(cfg.n_layers - nd))
        outs.append(c2)
        new = (jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)
               if len(outs) > 1 else outs[0])
        return x, {"mla": new}

    def _decode_rwkv(self, params, cache, x):
        cfg = self.cfg

        def body(h, xs):
            p, c = xs
            hh = layer_norm(h, p["ln1_s"], p["ln1_b"], cfg.norm_eps)
            tm_out, c_tm = rwkv6.decode_time_mix(p["tm"], hh, c,
                                                 n_heads=cfg.n_heads,
                                                 rwkv_cfg=cfg.rwkv)
            h = h + tm_out
            hh = layer_norm(h, p["ln2_s"], p["ln2_b"], cfg.norm_eps)
            cm_out, cm_x = rwkv6.decode_channel_mix(p["cm"], hh, c)
            h = h + cm_out
            new_c = {"tm_x": c_tm["tm_x"], "cm_x": cm_x, "wkv": c_tm["wkv"]}
            return h, new_c

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                    unroll=flags.scan_unroll(cfg.n_layers))
        return x, new_cache

    def _decode_vlm(self, params, cache, x, pos):
        cfg = self.cfg

        def group(h, xs):
            gp, kc, vc, ck, cv = xs

            def self_body(hh, ys):
                p, k1, v1 = ys
                hh, k1, v1, _ = self._decode_block(p, hh, k1, v1, pos, None)
                return hh, (k1, v1)

            per = cfg.n_layers // cfg.vision.n_cross_layers
            h, (kc, vc) = jax.lax.scan(self_body, h, (gp["self"], kc, vc),
                                       unroll=flags.scan_unroll(per))
            p = gp["cross"]
            hh = rms_norm(h, p["ln1"], cfg.norm_eps)
            a = attn.decode_cross_attention(p["xattn"], hh, ck, cv, cfg=cfg)
            h = h + jnp.tanh(p["gate_attn"]).astype(h.dtype) * a
            hh = rms_norm(h, p["ln2"], cfg.norm_eps)
            h = h + jnp.tanh(p["gate_mlp"]).astype(h.dtype) * apply_mlp(
                p["mlp"], hh, cfg.act)
            return h, (kc, vc)

        xs = (params["groups"], cache["k"], cache["v"], cache["ck"], cache["cv"])
        x, (k, v) = jax.lax.scan(
            group, x, xs,
            unroll=flags.scan_unroll(cfg.vision.n_cross_layers))
        return x, {"k": k, "v": v, "ck": cache["ck"], "cv": cache["cv"]}

    def _decode_encdec(self, params, cache, x, pos):
        cfg = self.cfg

        def body(h, xs):
            p, kc, vc, ck, cv = xs
            hh = rms_norm(h, p["ln1"], cfg.norm_eps)
            a, kc, vc = attn.decode_self_attention(p["attn"], hh, kc, vc, pos,
                                                   cfg=cfg, rope=False)
            h = h + a
            hh = rms_norm(h, p["lnx"], cfg.norm_eps)
            h = h + attn.decode_cross_attention(p["xattn"], hh, ck, cv, cfg=cfg)
            hh = rms_norm(h, p["ln2"], cfg.norm_eps)
            h = h + apply_mlp(p["mlp"], hh, cfg.act)
            return h, (kc, vc)

        xs = (params["layers"], cache["k"], cache["v"], cache["ck"], cache["cv"])
        x, (k, v) = jax.lax.scan(body, x, xs,
                                 unroll=flags.scan_unroll(cfg.n_layers))
        return x, {"k": k, "v": v, "ck": cache["ck"], "cv": cache["cv"]}

    # ================================================================ prefill
    def prefill(self, params, batch) -> Tuple[jax.Array, Params]:
        """Forward over the prompt, returning (last-token logits, filled cache).

        For the dry-run roofline the cost is dominated by the forward pass;
        cache fill is included for attention families.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"]["w"], tokens)
        x = constrain(x, ("batch", "seq", "embed"))
        positions = jnp.arange(s)
        if cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
            x = x + sinusoidal_pos(s, cfg.d_model).astype(self.dtype)
            body = _remat(lambda h, p: self._encdec_block_fwd(
                p, h, memory, positions), "none")
            x, _ = jax.lax.scan(lambda h, p: (body(h, p), None), x,
                                params["layers"],
                                unroll=flags.scan_unroll(cfg.n_layers))
        elif cfg.family == "vlm":
            vis = batch["patches"].astype(self.dtype) @ params["vis_proj"]
            x, _ = self._backbone_vlm(params, x, vis, positions)
        else:
            x, _ = self._backbone(params, x, positions)
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits_last = h[:, -1, :] @ params["lm_head"]["w"]
        return logits_last, None
