"""seamless-m4t-large-v2 [audio] — encoder-decoder [arXiv:2308.11596; hf].
24L d_model=1024 16H (kv=16) d_ff=8192 vocab=256206.
The speech frontend is a stub: the batch feeds precomputed frame
embeddings; 24 encoder + 24 decoder layers with cross-attention."""

from repro.models.config import ModelConfig, register

register(ModelConfig(
    name="seamless-m4t-large-v2",
    family="audio",
    is_encoder_decoder=True,
    num_layers=24,
    num_encoder_layers=24,
    d_model=1024,
    num_heads=16,
    num_kv_heads=16,
    head_dim=64,
    d_ff=8192,
    vocab_size=256206,
    rope_theta=10_000.0,
    frontend="audio",
))
