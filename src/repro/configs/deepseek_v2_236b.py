"""deepseek-v2-236b — DeepSeek-V2 (arXiv:2405.04434) as its config.json
publishes it: 60 layers of multi-head latent attention (kv_lora 512,
q_lora 1536, 128 + 64 rope dims per head, YaRN rope scaling 40x); layer 0
has a SwiGLU MLP of width 12,288 (``first_k_dense_replace`` 1), the other
59 have 2 shared experts and 160 routed experts of width 1,536, top-6 by
group-limited greedy routing (8 groups, the best 3 kept; softmax scores,
weights scaled by 16, not renormalised).
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,
    d_ff=12288,
    vocab=102400,
    act="silu",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    n_dense_layers=1,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=160, top_k=6, n_shared=2, d_ff_expert=1536,
                  n_group=8, topk_group=3, norm_topk_prob=False,
                  routed_scaling_factor=16.0),
)
