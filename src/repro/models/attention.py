"""Attention variants: GQA (+ qk-norm / QKV-bias / sliding-window / M-RoPE)
and MLA (multi-head latent attention).

Sequence-level attention is a memory-bounded chunked online-softmax
("flash-style") implementation in pure jnp, :func:`chunked_attention`.
:func:`gqa_forward` runs the fused Pallas kernel of
``repro.kernels.flash_attention`` in its place where that kernel is
compiled for a TPU (``kernels.compat.compiled_for_tpu``) and each kv
head's query heads fill whole lane tiles (``flash_attention.lane_tiled``);
its gradient is the VJP of :func:`chunked_attention`.  MLA and
cross-attention always take the jnp path.  The core of both paths runs
under the ``attention_core`` scope, and the ``attention`` metrics scope
counts each trace of either path (counters ``fused``, ``chunked``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import compat
from repro.kernels.flash_attention import flash_attention, lane_tiled
from repro.obs import metrics as obs_metrics

from .config import ModelConfig
from .layers import ParamDef, apply_mrope, apply_rope, rms_norm

NEG_INF = -1e30

#: kv positions a step of :func:`chunked_attention` sweeps
CHUNK = 512

#: which attention path each trace of :func:`gqa_forward` took
_PATHS = obs_metrics.scope("attention")


# ==========================================================================
# GQA
# ==========================================================================

def gqa_table(cfg: ModelConfig) -> dict[str, ParamDef]:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = {
        "wq": ParamDef((D, H * hd)),
        "wk": ParamDef((D, KV * hd)),
        "wv": ParamDef((D, KV * hd)),
        "wo": ParamDef((H * hd, D)),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamDef((H * hd,), init="zeros")
        t["bk"] = ParamDef((KV * hd,), init="zeros")
        t["bv"] = ParamDef((KV * hd,), init="zeros")
    if cfg.qk_norm:
        t["q_norm"] = ParamDef((hd,), init="ones")
        t["k_norm"] = ParamDef((hd,), init="ones")
    return t


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_type == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      chunk: int = CHUNK) -> jax.Array:
    """Online-softmax attention, scanned over KV chunks.

    q: (B, Sq, H, hd);  k, v: (B, Sk, KV, hd) with H % KV == 0.
    ``causal`` masks j > i (+ Sk - Sq offset); ``window`` > 0 additionally
    masks j <= i - window (sliding window).  Returns (B, Sq, H, vd).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, vd = v.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scale = hd ** -0.5
    nchunks = -(-Sk // chunk)
    pad = nchunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunks, chunk, KV, hd)
    vc = v.reshape(B, nchunks, chunk, KV, vd)
    q_pos = jnp.arange(Sq) + (Sk - Sq)        # absolute position of queries

    def step(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp                        # kj: (B, C, KV, hd)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg.astype(jnp.float32),
                       kj.astype(jnp.float32)) * scale
        kv_pos = j * chunk + jnp.arange(chunk)           # (C,)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        mask &= (kv_pos < Sk)[None, :]
        s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p_ = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p_.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p_, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, vd), jnp.float32)
    # checkpoint the chunk body: without this the backward pass stores
    # every chunk's (blk_q x blk_k) score tile in f32 — O(S^2) memory,
    # exactly what flash attention exists to avoid.  With it, backward
    # recomputes scores per chunk from q/k/v (the flash backward).
    step_ckpt = jax.checkpoint(
        step, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(
        step_ckpt, (m0, l0, a0),
        (jnp.arange(nchunks), jnp.moveaxis(kc, 1, 0),
         jnp.moveaxis(vc, 1, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, vd)   # b k g q d -> b q (kg) d
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                    window: int, chunk: int) -> jax.Array:
    """The Pallas kernel on :func:`chunked_attention`'s layout, under the
    ``attention_core`` scope (the head-major transposes are outside it:
    XLA folds them into the q/k norm and RoPE fusion); its gradient is
    that of :func:`chunked_attention` with ``chunk``, recomputed from q,
    k and v."""
    qh, kh, vh = (x.swapaxes(1, 2) for x in (q, k, v))
    with jax.named_scope("attention_core"):
        return flash_attention(qh, kh, vh, causal=causal, window=window)


def _fused_fwd(q, k, v, causal, window, chunk):
    return fused_attention(q, k, v, causal, window, chunk), (q, k, v)


def _fused_bwd(causal, window, chunk, res, g):
    _, vjp = jax.vjp(functools.partial(chunked_attention, causal=causal,
                                       window=window, chunk=chunk), *res)
    return vjp(g)


fused_attention.defvjp(_fused_fwd, _fused_bwd)


def gqa_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array, causal: bool = True) -> jax.Array:
    """Full-sequence GQA: (B, S, D) -> (B, S, D)."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    if (compat.compiled_for_tpu()
            and lane_tiled(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)):
        _PATHS.counter("fused").inc()
        out = fused_attention(q, k, v, causal, cfg.sliding_window, CHUNK)
    else:
        _PATHS.counter("chunked").inc()
        with jax.named_scope("attention_core"):
            out = chunked_attention(q, k, v, causal=causal,
                                    window=cfg.sliding_window)
    B, S, H, hd = q.shape
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                      p["wo"].astype(x.dtype))


# ==========================================================================
# MLA
# ==========================================================================

def mla_table(cfg: ModelConfig) -> dict[str, ParamDef]:
    D, H = cfg.d_model, cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qlr, kvlr = cfg.q_lora_rank, cfg.kv_lora_rank
    t = {
        "kv_down": ParamDef((D, kvlr + rope_d)),
        "kv_norm": ParamDef((kvlr,), init="ones"),
        "kv_up_k": ParamDef((kvlr, H * nope)),
        "kv_up_v": ParamDef((kvlr, H * vd)),
        "wo": ParamDef((H * vd, D)),
    }
    if qlr:
        t["q_down"] = ParamDef((D, qlr))
        t["q_norm"] = ParamDef((qlr,), init="ones")
        t["q_up"] = ParamDef((qlr, H * (nope + rope_d)))
    else:
        t["wq"] = ParamDef((D, H * (nope + rope_d)))
    return t


def _mla_q(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["q_down"].astype(x.dtype)),
                      p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rh->bsh", cq, p["q_up"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    q = q.reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    kvlr = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dr->bsr", x, p["kv_down"].astype(x.dtype))
    latent, k_rope = ckv[..., :kvlr], ckv[..., kvlr:]
    latent = rms_norm(latent, p["kv_norm"], cfg.norm_eps)
    # single shared rope key "head"
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return latent, k_rope


def mla_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    """Full-sequence MLA (non-absorbed: expand latent, run chunked attn)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    latent, k_rope = _mla_latent(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rh->bsh", latent,
                        p["kv_up_k"].astype(x.dtype)).reshape(B, S, H, nope)
    v = jnp.einsum("bsr,rh->bsh", latent,
                   p["kv_up_v"].astype(x.dtype)).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope_d))],
        axis=-1)
    out = chunked_attention(q, k, v, causal=True)
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * vd),
                      p["wo"].astype(x.dtype))
