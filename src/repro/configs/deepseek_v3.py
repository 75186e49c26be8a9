"""deepseek-v3 — MoE with multi-head latent attention, priced for serving
traffic only.

[arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3 config.json]  61L
d_model=7168 128H; every layer MLA (q_lora_rank 1536, kv_lora_rank 512,
qk_nope 128, qk_rope 64, v 128); the first 3 layers dense (d_ff 18432),
the other 58 MoE with 256 routed experts of width 2048, top-8, and 1
shared expert; vocab=129280, untied; 1 multi-token prediction module.
671B parameters, ~37B active a token.

No model in ``repro.models`` builds it (there is no MLA block there), so
it is registered for traffic pricing only
(``repro.configs.registry.traffic_config``), outside ``arch_ids()``.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    head_dim=128,
    d_ff=18432,
    vocab_size=129280,
    rope_theta=10000.0,
    num_experts=256,
    experts_per_token=8,
    moe_d_ff=2048,
    shared_experts=1,
    first_k_dense=3,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mtp_layers=1,
)
