"""OPT-6.7B: the paper's MHA evaluation model, as published.

Pre-LN decoder: learned absolute positions (row = position + 2, a table of
max_position_embeddings + 2 rows), biases on the q/k/v/out and fc1/fc2
projections, ReLU MLP, affine LayerNorm eps 1e-5, tied unembedding.
[arXiv:2205.01068; hf:facebook/opt-6.7b config.json]
"""
from .base import ModelConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="opt-6.7b", family="dense",
        n_layers=32, d_model=4096, n_heads=32, n_kv_heads=32,
        d_ff=16384, vocab_size=50272, head_dim=128,
        norm="layernorm", norm_eps=1e-5, act="relu", bias=True,
        use_rope=False, abs_positions="learned",
        tie_embeddings=True, max_seq=2048,
    )


def get_smoke_config() -> ModelConfig:
    return get_config().replace(
        name="opt-6.7b-smoke", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256)
