"""Encoder–decoder transformer (seamless-m4t-large-v2 backbone).

The speech frontend is a stub: the batch feeds precomputed frame
embeddings (B, S_enc, D).  The encoder is a bidirectional transformer over
frames; the decoder is a causal LM with cross-attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention as attn
from .config import ModelConfig
from .layers import (ParamDef, embed_table, embed_tokens, init_table,
                     lm_logits, mlp_forward, mlp_table, rms_norm)


def cross_table(cfg: ModelConfig) -> dict[str, ParamDef]:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ParamDef((D, H * hd)),
        "wk": ParamDef((D, KV * hd)),
        "wv": ParamDef((D, KV * hd)),
        "wo": ParamDef((H * hd, D)),
    }


def _cross_kv(cfg, p, enc_out):
    B, Se, _ = enc_out.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dh->bsh", enc_out,
                   p["wk"].astype(enc_out.dtype)).reshape(B, Se, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", enc_out,
                   p["wv"].astype(enc_out.dtype)).reshape(B, Se, KV, hd)
    return k, v


def cross_forward(cfg: ModelConfig, p: dict, x: jax.Array, k, v) -> jax.Array:
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x,
                   p["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    out = attn.chunked_attention(q, k, v, causal=False)
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                      p["wo"].astype(x.dtype))


# -- layer tables -------------------------------------------------------------

def enc_block_tables(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    return {
        "attn": attn.gqa_table(cfg),
        "norm_attn": {"scale": ParamDef((D,), init="ones")},
        "mlp": mlp_table(D, cfg.d_ff),
        "norm_mlp": {"scale": ParamDef((D,), init="ones")},
    }


def dec_block_tables(cfg: ModelConfig) -> dict:
    t = enc_block_tables(cfg)
    t["cross"] = cross_table(cfg)
    t["norm_cross"] = {"scale": ParamDef((cfg.d_model,), init="ones")}
    return t


def _init_block(tables: dict, key, dtype) -> dict:
    keys = jax.random.split(key, len(tables))
    return {name: init_table(k, tbl, dtype)
            for (name, tbl), k in zip(sorted(tables.items()), keys)}


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    k_emb, k_enc, k_dec = jax.random.split(key, 3)
    params = {
        "embed": init_table(
            k_emb,
            embed_table(cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings),
            dtype),
        "enc_norm": {"scale": jnp.ones((cfg.d_model,), dtype)},
    }
    enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    params["enc_layers"] = jax.vmap(
        lambda k: _init_block(enc_block_tables(cfg), k, dtype))(enc_keys)
    params["dec_layers"] = jax.vmap(
        lambda k: _init_block(dec_block_tables(cfg), k, dtype))(dec_keys)
    return params


# -- forward ------------------------------------------------------------------

def _enc_block(cfg, p, x, positions):
    h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
    x = x + attn.gqa_forward(cfg, p["attn"], h, positions, causal=False)
    h = rms_norm(x, p["norm_mlp"]["scale"], cfg.norm_eps)
    return x + mlp_forward(p["mlp"], h, cfg.act)


def _dec_block(cfg, p, x, positions, enc_out):
    h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
    x = x + attn.gqa_forward(cfg, p["attn"], h, positions, causal=True)
    h = rms_norm(x, p["norm_cross"]["scale"], cfg.norm_eps)
    ck, cv = _cross_kv(cfg, p["cross"], enc_out)
    x = x + cross_forward(cfg, p["cross"], h, ck, cv)
    h = rms_norm(x, p["norm_mlp"]["scale"], cfg.norm_eps)
    return x + mlp_forward(p["mlp"], h, cfg.act)


def encode(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    B, S, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    block = functools.partial(_enc_block, cfg)
    if cfg.remat != "none":
        block = jax.checkpoint(block)

    def body(h, lp):
        return block(lp, h, positions), None

    x, _ = jax.lax.scan(body, frames.astype(jnp.dtype(cfg.dtype)),
                        params["enc_layers"])
    return rms_norm(x, params["enc_norm"]["scale"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """batch: {"frames": (B,Se,D), "tokens": (B,Sd)} -> logits (B,Sd,V)."""
    enc_out = encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    B, Sd = tokens.shape
    x = embed_tokens(params["embed"], tokens, jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32)[None],
                                 (B, Sd))
    block = functools.partial(_dec_block, cfg)
    if cfg.remat != "none":
        block = jax.checkpoint(block)

    def body(h, lp):
        return block(lp, h, positions, enc_out), None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = rms_norm(x, params["embed"]["final_norm"], cfg.norm_eps)
    return lm_logits(params["embed"], x, cfg.tie_embeddings)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    from .transformer import cross_entropy
    logits = forward(cfg, params, batch)
    return cross_entropy(logits, batch["labels"], batch.get("loss_mask"),
                         real_vocab=cfg.vocab_size)
