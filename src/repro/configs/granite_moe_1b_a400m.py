"""granite-moe-1b-a400m [moe] — 32 experts top-8
[hf:ibm-granite/granite-3.0-1b-a400m-base].  24L d_model=1024 16H (GQA kv=8)
per-expert d_ff=512 vocab=49155."""

from repro.models.config import ModelConfig, register

register(ModelConfig(
    name="granite-moe-1b-a400m",
    family="moe",
    num_layers=24,
    d_model=1024,
    num_heads=16,
    num_kv_heads=8,
    head_dim=64,
    d_ff=512,
    vocab_size=49155,
    num_experts=32,
    num_experts_per_tok=8,
    rope_theta=10_000.0,
))
