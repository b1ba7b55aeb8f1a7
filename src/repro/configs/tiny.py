"""Reduced same-family configs for CPU smoke tests: small widths, few
layers/experts, tiny vocab — the structure (attention flavour, MoE, SSM,
enc-dec, M-RoPE) is preserved exactly."""

from repro.models.config import ModelConfig, get_config


def tiny_config(name: str) -> ModelConfig:
    cfg = get_config(name)
    kw = dict(
        name=cfg.name + "-tiny",
        num_layers=2,
        d_model=64,
        vocab_size=256,
        dtype="float32",
        remat="none",
    )
    if cfg.has_attention:
        if cfg.attention == "mla":
            kw.update(num_heads=4, num_kv_heads=4, head_dim=16,
                      q_lora_rank=24, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
        else:
            ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
            kv = max(1, 4 // min(ratio, 4))
            kw.update(num_heads=4, num_kv_heads=kv, head_dim=16)
    if cfg.d_ff > 0:
        kw.update(d_ff=96)
    if cfg.is_moe:
        kw.update(num_experts=4,
                  num_experts_per_tok=min(cfg.num_experts_per_tok, 2))
    if cfg.has_ssm:
        kw.update(ssm_d_inner=128, ssm_state=8, ssm_dt_rank=8)
    if cfg.sliding_window:
        kw.update(sliding_window=16)
    if cfg.is_encoder_decoder:
        kw.update(num_encoder_layers=2)
    if cfg.rope_type == "mrope":
        kw.update(mrope_sections=(2, 3, 3))
    if cfg.attn_layer_period:
        # one whole period with both kinds of layer: ssm, attention, ssm, ssm
        kw.update(num_layers=4, attn_layer_period=4, attn_layer_offset=1)
    return cfg.replace(**kw)
