"""demo-100m — ~100M-parameter llama-like LM for the migration tests.

Not an assigned architecture; used by the hybrid migration tests (the
paper's own workloads are notebook pipelines, so this plays the role of its
"model fitting" cell at a size that trains on CPU).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="demo-100m",
    family="dense",
    num_layers=12,
    d_model=768,
    num_heads=12,
    num_kv_heads=12,
    d_ff=2048,
    vocab_size=32_768,
    tie_embeddings=True,
)

REDUCED = CONFIG.reduced()
