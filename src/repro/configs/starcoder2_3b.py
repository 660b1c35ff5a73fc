"""StarCoder2-3B: GQA kv=2, RoPE, LayerNorm, plain-GELU MLP, biases on every
projection, a 4,096-token sliding window in every layer
[arXiv:2402.19173; hf bigcode/starcoder2-3b config.json]."""
from repro.configs.base import ModelConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="starcoder2-3b", family="dense",
        n_layers=30, d_model=3072, n_heads=24, n_kv_heads=2,
        d_ff=12288, vocab_size=49152,
        default_layer="swa", window=4096,
        norm="layernorm", norm_eps=1e-5, mlp_gated=False, mlp_act="gelu",
        qkv_bias=True, o_bias=True, mlp_bias=True, tie_embeddings=True,
        rope_theta=999999.4420358813,
    )


def get_smoke_config() -> ModelConfig:
    """The same block at toy widths; the window is shorter than the test
    sequences, so the decode ring wraps."""
    return ModelConfig(
        name="starcoder2-3b-smoke", family="dense",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=256,
        default_layer="swa", window=8,
        norm="layernorm", norm_eps=1e-5, mlp_gated=False, mlp_act="gelu",
        qkv_bias=True, o_bias=True, mlp_bias=True, tie_embeddings=True,
        rope_theta=999999.4420358813, remat=False,
    )
