"""qwen2-vl-7b [vlm] — M-RoPE, dynamic resolution [arXiv:2409.12191; hf].
28L d_model=3584 28H (GQA kv=4) d_ff=18944 vocab=152064.
The vision tower is a stub: the batch feeds precomputed patch
embeddings (B, S, d_model) + 3D (t,h,w) position ids for M-RoPE."""

from repro.models.config import ModelConfig, register

register(ModelConfig(
    name="qwen2-vl-7b",
    family="vlm",
    num_layers=28,
    d_model=3584,
    num_heads=28,
    num_kv_heads=4,
    head_dim=128,
    d_ff=18944,
    vocab_size=152064,
    rope_type="mrope",
    mrope_sections=(16, 24, 24),
    rope_theta=1_000_000.0,
    frontend="vision",
))
