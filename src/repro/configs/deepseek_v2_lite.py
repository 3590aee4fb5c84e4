"""deepseek-v2-lite [moe] — arXiv:2405.04434 (hf: deepseek-ai/DeepSeek-V2-Lite).

27L, d_model 2048, 16 heads, MLA without query compression (kv_lora 512,
nope 128, rope 64, v 128), YaRN rotary (factor 40 over 4096 positions,
mscale = mscale_all_dim = 0.707, theta 10000); MoE: 64 routed experts top-6
+ 2 shared, expert d_ff 1408, softmax router without renormalisation; 1
leading dense layer with d_ff 10944; vocab 102400, untied.
"""

from .base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    attention="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, num_shared=2,
                  router="softmax", num_dense_layers=1, dense_d_ff=10944),
    rope_theta=10000.0,
    yarn=YaRNConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
)

SMOKE = CONFIG.with_overrides(
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, rope_head_dim=8,
                  nope_head_dim=16, v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=3, d_expert=32, num_shared=2,
                  router="softmax", num_dense_layers=1, dense_d_ff=128),
    yarn=YaRNConfig(factor=40.0, original_max_position_embeddings=16,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    q_block=16,
    k_block=16,
)
