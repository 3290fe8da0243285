"""deepseek-v2-lite [moe] — MLA without a query latent (kv_lora_rank 512,
YaRN rope x40), one leading dense layer, then 64 routed experts top-6 with
softmax gates left unnormalized plus 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv 2405.04434]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,          # MLA: per-head keys and values from the latent
    head_dim=128,
    d_ff=10944,               # the leading dense layer's SwiGLU width
    vocab_size=102400,
    attention="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    qk_nope_head_dim=128,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    act="silu",
    num_experts=64,
    top_k=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    shared_experts=2,
    first_k_dense=1,
    tie_embeddings=False,
    norm_eps=1e-6,
    max_position=163_840,
)
