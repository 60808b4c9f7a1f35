"""deepseek-v2-lite [moe] — latent attention (MLA), 64 routed experts
top-6 + 2 shared, one leading dense layer, yarn RoPE.

[hf:deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102_400, rope_theta=10_000.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, first_k_dense=1,
    rope_scaling={"type": "yarn", "factor": 40.0,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32.0, "beta_slow": 1.0,
                  "mscale": 0.707, "mscale_all_dim": 0.707},
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, d_ff_shared=2816,
                  norm_topk_prob=False, shared_gate=False,
                  router_f32=True),
)
