"""deepseek-v2-lite-16b [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite
config.json]: 27L d=2048 16H MLA (kv_lora=512, q full rank, nope 128,
rope 64 with YaRN factor 40, v 128); the first layer dense (d_ff 10944),
then MoE: 64 routed experts (width 1408) top-6 by softmax, gates not
renormalized, plus 2 shared experts; vocab=102400, untied head."""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab_size=102400,
    attention="mla",
    act="silu",
    mla=MLAConfig(
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=YarnScaling(
            factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707,
        ),
    ),
    moe=MoEConfig(num_experts=64, experts_per_token=6, d_ff_expert=1408,
                  num_shared_experts=2, first_dense_layers=1, norm_topk_prob=False),
)
