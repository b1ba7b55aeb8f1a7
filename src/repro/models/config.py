"""Model configuration: one dataclass covering all assigned families
(dense GQA / MLA / MoE / SSM / hybrid / VLM backbone / enc-dec audio).

A ``hybrid`` config runs attention and the SSM side by side in every layer
(hymba), unless ``attn_layer_period`` is set: then each layer has one mixer,
attention where ``i % attn_layer_period == attn_layer_offset`` and the SSM
elsewhere (Jamba's interleaved stack)."""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # attention flavour
    attention: str = "gqa"           # gqa | mla | none
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rope_type: str = "rope"          # rope | mrope | none
    mrope_sections: tuple = ()       # e.g. (16, 24, 24) halves of head_dim
    sliding_window: int = 0          # 0 = full causal attention

    # MLA (multi-head latent attention)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25

    # SSM (Mamba-1)
    ssm_state: int = 0
    ssm_d_inner: int = 0
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    ssm_inner_norms: bool = False    # RMSNorm on dt, B, C (Jamba's mixer)
    # initial A_log and dt bias: "constant" (ones, zeros) or "mamba" (the
    # Mamba paper's: A = -(1..N), dt log-uniform on [1e-3, 1e-1])
    ssm_init: str = "constant"

    # interleaved layers (hybrid family): attention where
    # i % attn_layer_period == attn_layer_offset, the SSM elsewhere;
    # 0 = every layer alike
    attn_layer_period: int = 0
    attn_layer_offset: int = 0

    # encoder-decoder (audio family)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # modality frontend stub: "none" | "vision" | "audio"
    frontend: str = "none"

    # numerics
    act: str = "silu"
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"          # forward compute/param dtype
    tie_embeddings: bool = False

    # per-block rematerialisation of the layer stack
    remat: str = "full"              # none | block | full
    # time steps a block of the jnp selective scan covers; 0 = the whole
    # sequence
    ssm_block: int = 256

    def __post_init__(self):
        if self.rope_type not in ("rope", "mrope", "none"):
            raise ValueError(f"rope_type {self.rope_type!r}: rope | mrope | "
                             f"none")
        if self.ssm_init not in ("constant", "mamba"):
            raise ValueError(f"ssm_init {self.ssm_init!r}: constant | mamba")
        if self.attn_layer_period and not (
                self.family == "hybrid"
                and 0 <= self.attn_layer_offset < self.attn_layer_period):
            raise ValueError("attn_layer_period needs the hybrid family and "
                             "0 <= attn_layer_offset < attn_layer_period")
        if self.attention == "gqa" and self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        if self.family in ("ssm", "hybrid") and not self.ssm_d_inner:
            object.__setattr__(self, "ssm_d_inner", 2 * self.d_model)
        if self.family in ("ssm", "hybrid") and not self.ssm_dt_rank:
            object.__setattr__(self, "ssm_dt_rank",
                               math.ceil(self.d_model / 16))

    # -- derived sizes -------------------------------------------------------

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 (Megatron-style); the loss
        masks the padding columns."""
        return -(-self.vocab_size // 256) * 256

    @property
    def q_head_dim(self) -> int:
        if self.attention == "mla":
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def has_attention(self) -> bool:
        return self.attention != "none"

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def layer_kinds(self) -> tuple:
        """Each layer's mixer, in order, for an interleaved stack:
        ``"attention"`` or ``"ssm"``; empty where every layer is alike."""
        if not self.attn_layer_period:
            return ()
        return tuple("attention" if i % self.attn_layer_period
                     == self.attn_layer_offset else "ssm"
                     for i in range(self.num_layers))

    def kind_config(self, kind: str) -> "ModelConfig":
        """The config one kind of layer of an interleaved stack is built
        and run with: attention only, or the SSM only."""
        flat = dict(attn_layer_period=0, attn_layer_offset=0)
        if kind == "attention":
            return self.replace(family="dense", **flat)
        return self.replace(family="ssm", attention="none", **flat)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- parameter counting (for roofline MODEL_FLOPS) ------------------------

    def param_count(self) -> tuple[int, int]:
        """Returns (total_params, active_params) — active differs for MoE."""
        D, L = self.d_model, self.num_layers
        emb = self.vocab_size * D
        total = active = 0

        def attn_params() -> int:
            if self.attention == "mla":
                p = 0
                if self.q_lora_rank:
                    p += D * self.q_lora_rank + self.q_lora_rank  # down + norm
                    p += self.q_lora_rank * self.num_heads * self.q_head_dim
                else:
                    p += D * self.num_heads * self.q_head_dim
                p += D * (self.kv_lora_rank + self.qk_rope_head_dim)
                p += self.kv_lora_rank
                p += self.kv_lora_rank * self.num_heads * (
                    self.qk_nope_head_dim + self.v_head_dim)
                p += self.num_heads * self.v_head_dim * D
                return p
            if self.attention == "none":
                return 0
            hd = self.head_dim
            p = D * self.num_heads * hd + 2 * D * self.num_kv_heads * hd \
                + self.num_heads * hd * D
            if self.qkv_bias:
                p += (self.num_heads + 2 * self.num_kv_heads) * hd
            if self.qk_norm:
                p += 2 * hd
            return p

        def mlp_params() -> tuple[int, int]:
            if self.is_moe:
                per = 3 * D * self.d_ff
                tot = self.num_experts * per + D * self.num_experts
                act = self.num_experts_per_tok * per + D * self.num_experts
                return tot, act
            if self.d_ff == 0:
                return 0, 0
            return 3 * D * self.d_ff, 3 * D * self.d_ff

        def ssm_params() -> int:
            if not self.has_ssm:
                return 0
            di, st, dr = self.ssm_d_inner, self.ssm_state, self.ssm_dt_rank
            inner_norms = dr + 2 * st if self.ssm_inner_norms else 0
            return (D * 2 * di + di * self.ssm_conv + di
                    + di * (dr + 2 * st) + dr * di + di
                    + di * st + di + di * D + inner_norms)

        a, (mt, ma), s = attn_params(), mlp_params(), ssm_params()
        norms = 2 * D
        if self.layer_kinds:
            # one mixer a layer: count each kind's layers with their own
            n_attn = self.layer_kinds.count("attention")
            mix = n_attn * a + (L - n_attn) * s
            total = mix + L * (mt + norms) + emb + D
            active = mix + L * (ma + norms) + emb + D
        else:
            layer_total = a + mt + s + norms
            layer_active = a + ma + s + norms
            total = L * layer_total + emb + D
            active = L * layer_active + emb + D
        if self.is_encoder_decoder:
            # encoder layers: self-attn + mlp; decoder adds cross-attn
            enc = self.num_encoder_layers * (a + mt + norms)
            total += enc + L * a          # cross-attn per decoder layer
            active += enc + L * a
        if not self.tie_embeddings:
            total += emb
            active += emb
        return total, active


_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    # configs are registered by importing repro.configs
    import repro.configs  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)
