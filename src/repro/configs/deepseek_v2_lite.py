"""deepseek-v2-lite — MLA without q-LoRA, YaRN, 64 routed top-6 + 2 shared
[hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H (MLA: kv_lora 512, qk 128+64, v 128) vocab=102400;
layer 0 dense (d_ff=10944), layers 1-26 MoE: 64 routed experts of 1408,
6 per token by a softmax router, gates not renormalised, 2 shared experts;
YaRN rope (factor 40 over 4096 positions, mscale 0.707). The sequence-level
balance loss is left out (its weight is not in the published config).
"""
from repro.configs.base import MLAConfig, MoEConfig, ModelConfig

ARCH_ID = "deepseek-v2-lite"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,
        d_ff=10944,                      # the dense layer 0
        vocab_size=102400,
        norm_eps=1e-6,
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      d_shared=1408, first_dense_layers=1,
                      norm_topk_prob=False, routed_scaling_factor=1.0,
                      aux_loss_weight=0.0),
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, rope_factor=40.0,
                      rope_original_max=4096, beta_fast=32.0, beta_slow=1.0,
                      mscale=0.707, mscale_all_dim=0.707),
        rope_theta=10000.0,
        source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
    )
