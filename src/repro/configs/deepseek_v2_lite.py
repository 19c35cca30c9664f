"""deepseek-v2-lite — MLA without q-LoRA, YaRN rope, one leading dense
layer, then DeepSeekMoE layers: 64 routed experts (top-6 softmax, not
renormalised) and 2 shared experts.

[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434 §2.1–2.2]
The latent cache holds 512 + 64 values a token per layer; the dropless
routing sorts each (token, expert) pair into a grouped matmul
(``kernels/grouped_matmul``) in prefill.
"""
from repro.configs.base import ModelConfig

config = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    first_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                # the routed (and shared) expert width
    dense_d_ff=10_944,        # the leading dense layer's MLP
    vocab_size=102_400,
    attn_type="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    head_dim=192,             # qk_nope + qk_rope
    n_experts=64,
    experts_per_token=6,
    n_shared_experts=2,
    capacity_factor=None,
    norm_topk_prob=False,
    rope_theta=10_000.0,
    rope_scaling=(("beta_fast", 32.0), ("beta_slow", 1.0),
                  ("factor", 40.0), ("mscale", 0.707),
                  ("mscale_all_dim", 0.707),
                  ("original_max_position_embeddings", 4096.0)),
    rope_interleave=True,
    norm_eps=1e-6,
    activation="silu",
    gated_mlp=True,
)
