"""step3 — Step-3's text decoder: MFA attention and 48-expert top-3 MoE.

[hf:stepfun-ai/step3 config.json; arXiv:2507.19427] d_model=7168,
vocab=128815, rope_theta=500000.  Attention (MFA): one projection to a
2048-wide query (share_q_dim), RMSNorm, then 64 heads of 256, against
one shared K and V head of 256 (num_attention_groups 1); RoPE over the
whole head.  MoE layers (4-59): a softmax router over 48 experts,
top-3, gates kept as routed (norm_expert_weight false), gated-SiLU
experts of 5120, plus one shared gated-SiLU expert of 5120 added to
the routed output.

The repo's layer stack is uniform, so CONFIG holds the 56 MoE layers;
the five dense layers (0-3 and 60, gated SiLU of 18432) are not in it.
rms_norm_eps 1e-5 is assumed (the config does not give it).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="step3",
    family="moe",
    num_layers=56,
    d_model=7168,
    num_heads=64,
    num_kv_heads=1,
    head_dim=256,
    q_lora_rank=2048,
    d_ff=5120,
    vocab_size=128815,
    num_experts=48,
    experts_per_token=3,
    moe_renorm=False,
    shared_expert_ff=5120,
    rope_theta=500_000.0,
    norm_eps=1e-5,
    source="hf:stepfun-ai/step3; arXiv:2507.19427",
)

# the same kinds of shape: one KV head under 8 query heads, a low-rank
# query, 8 of 48 routed experts held with top-3 routing, a shared
# expert, and a vocabulary slice
TINY = CONFIG.replace(
    name="step3-tiny",
    num_layers=2,
    d_model=64,
    num_heads=8,
    head_dim=32,
    q_lora_rank=32,
    d_ff=32,
    shared_expert_ff=32,
    vocab_size=256,
    experts_held=8,
    remat="none",
)
