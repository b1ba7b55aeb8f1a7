"""jamba2-3b [hybrid] — AI21-Jamba2-3B, interleaved Mamba-1 and attention
[hf:ai21labs/AI21-Jamba2-3B config.json].  28L d_model=2560: attention
where i % 14 == 7 (layers 7 and 21, the HF Jamba convention), Mamba-1
elsewhere (d_inner 5120, d_state 16, d_conv 4, dt_rank 160, RMSNorms on
dt, B and C; A and dt initialised as Mamba does); attention 20 heads of
128 on 1 KV head, no positional encoding; a dense SwiGLU MLP of 8192 in
every layer (num_experts 1); vocab 65536, tied embeddings, RMSNorm eps
1e-6."""

from repro.models.config import ModelConfig, register

register(ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    num_layers=28,
    d_model=2560,
    num_heads=20,
    num_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    rope_type="none",
    ssm_state=16,
    ssm_d_inner=5120,
    ssm_conv=4,
    ssm_dt_rank=160,
    ssm_inner_norms=True,
    ssm_init="mamba",
    attn_layer_period=14,
    attn_layer_offset=7,
    norm_eps=1e-6,
    tie_embeddings=True,
))
