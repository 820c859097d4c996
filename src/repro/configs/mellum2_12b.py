"""mellum2-12b-a2.5b [moe] — 28L d_model=2304 32H (GQA kv=4, head 128)
vocab=98304, untied head; every MLP sparse: 64 experts of 896, top-8
renormalised, no shared expert.  Layers repeat [sliding x3, full]: the
sliding ones see 1024 keys under plain rope, the full ones use YaRN
(factor 16 over 8192 positions); rope theta 500000 everywhere.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
import dataclasses
from .base import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,  # the dense width; every layer here is sparse
    vocab_size=98304,
    mlp_type="swiglu",
    rope_theta=500_000.0,
    moe=MoEConfig(num_experts=64, top_k=8, d_ff_expert=896),
    layer_pattern=("sliding", "sliding", "sliding", "full"),
    sliding_window=1024,
    rope_yarn=YarnConfig(
        factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.2772588722239782,
    ),
)

SMOKE_CONFIG = dataclasses.replace(
    CONFIG,
    num_layers=8, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=96, vocab_size=256,
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32),
    sliding_window=12,
    rope_yarn=dataclasses.replace(CONFIG.rope_yarn, original_max_position_embeddings=32),
    dtype="float32", remat=False,
)
