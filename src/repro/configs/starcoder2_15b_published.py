"""starcoder2-15b-published [dense] — StarCoder2-15B's published block:
LayerNorm with bias (eps 1e-5), a bias on every linear (``o`` included),
a 4,096-token sliding window on every layer, RoPE θ 1e5, tanh-GELU MLP
[arXiv:2402.19173; hf bigcode/starcoder2-15b config.json]."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="starcoder2-15b-published",
    family="dense",
    n_layers=40,
    d_model=6144,
    n_heads=48,
    n_kv_heads=4,
    d_ff=24576,
    vocab=49152,
    head_dim=128,
    qkv_bias=True,
    o_bias=True,
    rope_theta=1e5,
    norm="layer",
    norm_eps=1e-5,
    act="gelu",
    local_window=4096,
    pattern=("local",),
)
