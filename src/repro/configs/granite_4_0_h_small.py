"""granite-4.0-h-small — Mamba-2 + NoPE GQA hybrid, every layer followed by
a dropless 72-expert MoE and a shared MLP
[hf:ibm-granite/granite-4.0-h-small, model_type granitemoehybrid].

40L d_model=4096; 36 Mamba-2 mixers (128 heads of 64, d_state 128, chunk
256, conv 4 with bias, one group) and 4 attention mixers (32 query heads,
8 KV heads, head_dim 128, no positional encoding) at ``layer_types``
positions 5, 15, 25, 35: a period of 10.  Each mixer is followed by 72
routed SwiGLU experts of width 768, top-10 with a softmax over the ten
logits, plus an ungated shared SwiGLU of width 1536.  Embeddings x12,
residual branches x0.22, attention scores x1/128, logits /16; vocab
100,352, tied.
"""
from repro.configs.base import ModelConfig, period_pattern

# config.json's ``layer_types``, as published
LAYER_TYPES = (
    "mamba", "mamba", "mamba", "mamba", "mamba", "attention", "mamba",
    "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba",
    "attention", "mamba", "mamba", "mamba", "mamba", "mamba", "mamba",
    "mamba", "mamba", "mamba", "attention", "mamba", "mamba", "mamba",
    "mamba", "mamba", "mamba", "mamba", "mamba", "mamba", "attention",
    "mamba", "mamba", "mamba", "mamba",
)

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=768,
    vocab_size=100_352,
    pos_embed="nope",
    tie_embeddings=True,
    num_experts=72,
    experts_per_tok=10,
    shared_expert_d_ff=1536,
    shared_expert_gated=False,
    norm_topk_prob=True,      # softmax over the top-10 logits
    dropless=True,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_chunk=256,
    conv_width=4,
    conv_bias=True,
    block_pattern=period_pattern(LAYER_TYPES),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
)

REDUCED = CONFIG.reduced(num_kv_heads=2, num_experts=16, experts_per_tok=4)
