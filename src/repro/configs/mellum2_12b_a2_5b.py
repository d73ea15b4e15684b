"""mellum2-12b-a2.5b [moe] — 28L d_model=2304 32H (GQA kv=4, head_dim 128)
vocab=98304; a sparse MLP in every layer: 64 experts of width 896, top-8,
gates renormalised, no shared expert.  [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

Layers repeat (sliding, sliding, sliding, full): sliding layers attend over
the last 1024 positions with default RoPE, full layers over every position
with YaRN (factor 16 over 8192 original positions, beta 32/1, attention
factor 1.27726), both at theta 500,000.  The config gives no q/k norm; its
MTP head is not served.
"""
from repro.configs.base import ModelConfig

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab_size=98_304,
    rope_theta=500_000.0,
    layer_types=_PERIOD * 7,
    layer_window=1024,
    rope_yarn=(16.0, 8192.0, 32.0, 1.0, 1.2772588722239782),
    n_experts=64,
    top_k=8,
    norm_eps=1e-6,
    tie_embeddings=False,
    subquadratic=False,
)
