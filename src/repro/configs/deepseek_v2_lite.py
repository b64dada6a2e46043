"""deepseek-v2-lite [arXiv:2405.04434; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite]: 27 layers at d 2048, the first a dense SwiGLU of
10944, then 26 MoE layers of 64 routed experts (width 1408, top-6, gates
not renormalised) and 2 shared; MLA with no q LoRA (kv_lora 512, nope
128, rope 64, v 128) and YaRN RoPE (factor 40 over 4096 positions)."""
from repro.configs.base import MLACfg, ModelConfig, MoECfg, YarnCfg

YARN = YarnCfg(factor=40.0, original_max_position=4096, beta_fast=32.0,
               beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite", family="mla_moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=1408, vocab=102400,
        n_dense_layers=1, dense_d_ff=10944,
        moe=MoECfg(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                   norm_topk_prob=False, routed_scaling_factor=1.0),
        mla=MLACfg(kv_lora_rank=512, q_lora_rank=None,
                   qk_nope_dim=128, qk_rope_dim=64, v_dim=128),
        rope_theta=10000.0, rope_scaling=YARN, norm_eps=1e-6,
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-reduced", family="mla_moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=32, vocab=256,
        n_dense_layers=1, dense_d_ff=96,
        moe=MoECfg(n_experts=8, top_k=3, n_shared=2, d_expert=32,
                   norm_topk_prob=False, routed_scaling_factor=1.0),
        mla=MLACfg(kv_lora_rank=32, q_lora_rank=None,
                   qk_nope_dim=16, qk_rope_dim=8, v_dim=16),
        rope_theta=10000.0, rope_scaling=YARN, norm_eps=1e-6,
        dtype="float32", attn_block_q=32, attn_block_k=32,
    )
