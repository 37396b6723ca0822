"""qwen3-1.7b [hf:Qwen/Qwen3-1.7B]: 28L, d=2048, 16H GQA(kv=8), head_dim=128,
d_ff=6144, vocab=151936, qk-norm, tied embeddings, rope_theta=1e6."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-1.7b", family="dense",
    n_layers=28, d_model=2048, n_heads=16, n_kv_heads=8, head_dim=128,
    d_ff=6144, vocab=151936, qk_norm=True, tie_embeddings=True,
)
