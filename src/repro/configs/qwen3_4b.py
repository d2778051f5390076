"""qwen3-4b [dense] — qk_norm, GQA. [hf:Qwen/Qwen3-4B]

36L d_model=2560 32H (GQA kv=8) d_ff=9728 vocab=151936.
"""
from repro.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-4b",
    family="dense",
    n_layers=36,
    d_model=2560,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=9728,
    vocab=151936,
    act="silu",
    qk_norm=True,
    rope_theta=1e6,
    tie_embeddings=True,
    source="hf:Qwen/Qwen3-4B",
)
