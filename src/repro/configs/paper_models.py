"""Paper-side model configs.

The paper trains Qwen3-4B with GRPO on Search-R1.  We register:
  * ``qwen3-4b``       — the paper's base model (dense qwen3 family), dry-runnable.
  * ``search-r1-100m`` — a ~100M qwen3-family model for the e2e CPU training example.
  * ``tiny``           — a micro model used across unit tests and the quickstart.
"""
from repro.configs.base import ModelConfig, register

QWEN3_4B = register(ModelConfig(
    arch_id="qwen3-4b",
    family="dense",
    n_layers=36,
    d_model=2560,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=9728,
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1e6,
    tie_embeddings=True,        # HF Qwen/Qwen3-4B: "tie_word_embeddings": true
    long_context_window=32768,
))

SEARCH_R1_100M = register(ModelConfig(
    arch_id="search-r1-100m",
    family="dense",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=4,
    head_dim=64,
    d_ff=2048,
    vocab_size=4096,            # toy tokenizer vocab
    qk_norm=True,
    rope_theta=1e4,
    dtype="float32",
    tie_embeddings=True,
    remat=False,
))

TINY = register(ModelConfig(
    arch_id="tiny",
    family="dense",
    n_layers=2,
    d_model=128,
    n_heads=4,
    n_kv_heads=2,
    head_dim=32,
    d_ff=256,
    vocab_size=4096,
    qk_norm=True,
    rope_theta=1e4,
    dtype="float32",
    tie_embeddings=True,
    remat=False,
))
