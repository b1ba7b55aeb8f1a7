"""Plain float32 reference of a Jamba decoder over input embeddings, and the
first logits of its head at the last position.

Layers of two kinds, as HF ``JambaForCausalLM`` builds them from the
published ``config.json``: layer i is an attention layer where
``i % attn_layer_period == attn_layer_offset``, else a Mamba layer; with
``num_experts`` 1 every layer's feed-forward is a dense SwiGLU MLP.  Each
layer is RMSNorm, its mixer and the residual, then RMSNorm, the MLP and
the residual.  A final RMSNorm precedes the head.

The Mamba mixer (HF ``JambaMambaMixer``): an input projection to (x, z)
without bias; a causal depthwise convolution of width ``mamba_d_conv`` with
bias, then SiLU; a projection of x to (dt, B, C) without bias, each through
its own RMSNorm (``dt_layernorm``, ``b_layernorm``, ``c_layernorm``; Mamba-1
has none); dt through its own projection plus bias and softplus;
A = -exp(A_log); the selective scan, one time step after another,

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,    y_t = C_t . h_t + D x_t,

gated by SiLU(z), and an output projection without bias.

The attention (HF ``JambaAttention``): ``num_attention_heads`` query heads
of ``hidden_size / num_attention_heads`` on ``num_key_value_heads`` kv
heads, no bias and no positional encoding, causal softmax scaled by
1/sqrt(head_dim), output projection.

Layers run one at a time, each drawing its weights from the seed by the
recipe in ``common`` (``weights.recipe`` of the configuration), so float32
weights of one layer are resident at a time.  A_log and the dt bias are
drawn as Mamba initialises them (Gu & Dao, arXiv:2312.00752): A = -(1..N)
in every channel, dt log-uniform on [1e-3, 1e-1].
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import (draw, fan_in_std, head_weights, linear,
                              rms_norm, split_table)


class Dims(NamedTuple):
    D: int
    L: int
    period: int
    offset: int
    H: int
    KV: int
    hd: int
    F: int
    di: int
    N: int
    K: int
    R: int
    rows: int
    eps: float
    dtype: str
    tie: bool


def dims(config: dict) -> Dims:
    D, H = config["hidden_size"], config["num_attention_heads"]
    return Dims(D=D, L=config["num_hidden_layers"],
                period=config["attn_layer_period"],
                offset=config["attn_layer_offset"],
                H=H, KV=config["num_key_value_heads"], hd=D // H,
                F=config["intermediate_size"],
                di=config["mamba_expand"] * D, N=config["mamba_d_state"],
                K=config["mamba_d_conv"], R=config["mamba_dt_rank"],
                rows=config["weights"]["embedding_rows"],
                eps=float(config["rms_norm_eps"]),
                dtype=config["weights"]["dtype"],
                tie=bool(config.get("tie_word_embeddings", True)))


def is_attention(d: Dims, i: int) -> bool:
    return i % d.period == d.offset


def dt_bias(key, shape, dtype):
    """Mamba's initial dt bias: the inverse softplus of a step size drawn
    log-uniform on [1e-3, 1e-1] and floored at 1e-4, as float32 values of
    ``dtype``."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo)
                 + lo)
    dt = jnp.maximum(dt, 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype).astype(jnp.float32)


def _mlp_weights(d: Dims, key, normal) -> dict:
    m = split_table(key, ["w_down", "w_gate", "w_up"])
    return {"w_gate": normal(m["w_gate"], (d.D, d.F)),
            "w_up": normal(m["w_up"], (d.D, d.F)),
            "w_down": normal(m["w_down"], (d.F, d.D))}


@functools.partial(jax.jit, static_argnums=(0, 2))
def layer_weights(d: Dims, key, attention: bool) -> dict:
    dt = jnp.dtype(d.dtype)

    def normal(k, shape, std=None):
        return draw(k, shape, fan_in_std(shape) if std is None else std, dt)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    if attention:
        t = split_table(key, ["attn", "mlp", "norm_attn", "norm_mlp"])
        a = split_table(t["attn"], ["wk", "wo", "wq", "wv"])
        return {
            "wq": normal(a["wq"], (d.D, d.H * d.hd)),
            "wk": normal(a["wk"], (d.D, d.KV * d.hd)),
            "wv": normal(a["wv"], (d.D, d.KV * d.hd)),
            "wo": normal(a["wo"], (d.H * d.hd, d.D)),
            "norm": ones((d.D,)), "norm_mlp": ones((d.D,)),
        } | _mlp_weights(d, t["mlp"], normal)
    t = split_table(key, ["mlp", "norm_mlp", "norm_ssm", "ssm"])
    s = split_table(t["ssm"], ["A_log", "D", "b_norm", "c_norm", "conv_b",
                               "conv_w", "dt_bias", "dt_norm", "dt_proj",
                               "in_proj", "out_proj", "x_proj"])
    return {
        "in_proj": normal(s["in_proj"], (d.D, 2 * d.di)),
        "conv_w": normal(s["conv_w"], (d.K, d.di), 0.5),
        "conv_b": jnp.zeros((d.di,), jnp.float32),
        "x_proj": normal(s["x_proj"], (d.di, d.R + 2 * d.N)),
        "dt_norm": ones((d.R,)), "b_norm": ones((d.N,)),
        "c_norm": ones((d.N,)),
        "dt_proj": normal(s["dt_proj"], (d.R, d.di)),
        "dt_bias": dt_bias(s["dt_bias"], (d.di,), dt),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, d.N + 1, dtype=jnp.float32)), (d.di, d.N)).astype(
                dt).astype(jnp.float32),
        "D": ones((d.di,)),
        "out_proj": normal(s["out_proj"], (d.di, d.D)),
        "norm": ones((d.D,)), "norm_mlp": ones((d.D,)),
    } | _mlp_weights(d, t["mlp"], normal)


def mamba_mixer(d: Dims, w: dict, h, precision: str):
    B, S, _ = h.shape
    xz = linear(h, w["in_proj"], precision)
    xi, z = xz[..., :d.di], xz[..., d.di:]
    xp = jnp.concatenate([jnp.zeros((B, d.K - 1, d.di), jnp.float32), xi], 1)
    conv = sum(xp[:, i:i + S] * w["conv_w"][i] for i in range(d.K))
    xc = jax.nn.silu(conv + w["conv_b"])
    proj = linear(xc, w["x_proj"], precision)
    dt_in = rms_norm(proj[..., :d.R], w["dt_norm"], d.eps)
    Bt = rms_norm(proj[..., d.R:d.R + d.N], w["b_norm"], d.eps)
    Ct = rms_norm(proj[..., d.R + d.N:], w["c_norm"], d.eps)
    dt = jax.nn.softplus(linear(dt_in, w["dt_proj"], precision)
                         + w["dt_bias"])
    A = -jnp.exp(w["A_log"])                                 # (di, N)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp          # (B, di) (B, di) (B, N) (B, N)
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, ys = jax.lax.scan(
        step, jnp.zeros((B, d.di, d.N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (dt, xc, Bt, Ct)))
    y = (jnp.moveaxis(ys, 0, 1) + xc * w["D"]) * jax.nn.silu(z)
    return linear(y, w["out_proj"], precision)


def attention_mixer(d: Dims, w: dict, h, precision: str):
    B, S, _ = h.shape
    G = d.H // d.KV
    q = linear(h, w["wq"], precision).reshape(B, S, d.KV, G, d.hd)
    k = linear(h, w["wk"], precision).reshape(B, S, d.KV, d.hd)
    v = linear(h, w["wv"], precision).reshape(B, S, d.KV, d.hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(d.hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    return linear(o.reshape(B, S, d.H * d.hd), w["wo"], precision)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def block(d: Dims, w: dict, x, attention: bool, precision: str):
    mixer = attention_mixer if attention else mamba_mixer
    x = x + mixer(d, w, rms_norm(x, w["norm"], d.eps), precision)
    h = rms_norm(x, w["norm_mlp"], d.eps)
    u = jax.nn.silu(linear(h, w["w_gate"], precision)) \
        * linear(h, w["w_up"], precision)
    return x + linear(u, w["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def head(d: Dims, key, x, out_features: int, precision: str):
    """Final RMSNorm of the last position, then the output projection's
    first ``out_features`` logits."""
    w = head_weights(key, d.rows, d.D, d.tie, jnp.dtype(d.dtype),
                     out_features)
    h = rms_norm(x[:, -1], jnp.ones((d.D,), jnp.float32), d.eps)
    return linear(h, w, precision)


def forward(config: dict, seed: int, embeds: np.ndarray,
            precision: str = "float32") -> np.ndarray:
    """(R, S, D) float32 embeddings -> (R, out_features) float32 logits."""
    d = dims(config)
    k_emb, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    layer_keys = jax.random.split(k_layers, d.L)
    x = jnp.asarray(embeds, jnp.float32)
    for i in range(d.L):
        attn = is_attention(d, i)
        x = block(d, layer_weights(d, layer_keys[i], attn), x, attn,
                  precision)
    return np.asarray(head(d, k_emb, x, config["out_features"], precision))
