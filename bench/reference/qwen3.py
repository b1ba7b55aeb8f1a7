"""Plain float32 reference of a Qwen3 decoder over input embeddings, and the
first logits of its head at the last position.

A block, as the Qwen3 technical report and its published ``config.json``
describe it: RMSNorm, grouped-query attention with an RMSNorm over each
query and key head, rotary positions (theta from the config, the two halves
of each head rotated), causal softmax scaled by 1/sqrt(head_dim), output
projection and residual; then RMSNorm, a SwiGLU MLP and residual.  A final
RMSNorm precedes the head.  Layers run one at a time, each drawing its
weights from the seed by the recipe in ``common``, so float32 weights of one
layer are resident at a time.
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
    H: int
    KV: int
    hd: int
    F: int
    rows: int
    eps: float
    theta: float
    dtype: str
    tie: bool


def dims(config: dict) -> Dims:
    return Dims(D=config["hidden_size"], L=config["num_hidden_layers"],
                H=config["num_attention_heads"],
                KV=config["num_key_value_heads"], hd=config["head_dim"],
                F=config["intermediate_size"],
                rows=config["weights"]["embedding_rows"],
                eps=float(config["rms_norm_eps"]),
                theta=float(config["rope_theta"]),
                dtype=config["weights"]["dtype"],
                tie=bool(config.get("tie_word_embeddings", True)))


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(d: Dims, key) -> dict:
    dt = jnp.dtype(d.dtype)
    t = split_table(key, ["attn", "mlp", "norm_attn", "norm_mlp"])
    a = split_table(t["attn"], ["k_norm", "q_norm", "wk", "wo", "wq", "wv"])
    m = split_table(t["mlp"], ["w_down", "w_gate", "w_up"])

    def normal(k, shape):
        return draw(k, shape, fan_in_std(shape), dt)

    return {
        "wq": normal(a["wq"], (d.D, d.H * d.hd)),
        "wk": normal(a["wk"], (d.D, d.KV * d.hd)),
        "wv": normal(a["wv"], (d.D, d.KV * d.hd)),
        "wo": normal(a["wo"], (d.H * d.hd, d.D)),
        "w_gate": normal(m["w_gate"], (d.D, d.F)),
        "w_up": normal(m["w_up"], (d.D, d.F)),
        "w_down": normal(m["w_down"], (d.F, d.D)),
        "q_norm": jnp.ones((d.hd,), jnp.float32),
        "k_norm": jnp.ones((d.hd,), jnp.float32),
        "norm_attn": jnp.ones((d.D,), jnp.float32),
        "norm_mlp": jnp.ones((d.D,), jnp.float32),
    }


def rope(x, theta: float):
    """x: (B, S, heads, hd); rotates the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv    # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def block(d: Dims, w: dict, x, precision: str):
    B, S, _ = x.shape
    G = d.H // d.KV
    h = rms_norm(x, w["norm_attn"], d.eps)
    q = linear(h, w["wq"], precision).reshape(B, S, d.H, d.hd)
    k = linear(h, w["wk"], precision).reshape(B, S, d.KV, d.hd)
    v = linear(h, w["wv"], precision).reshape(B, S, d.KV, d.hd)
    q = rope(rms_norm(q, w["q_norm"], d.eps), d.theta)
    k = rope(rms_norm(k, w["k_norm"], d.eps), d.theta)
    qg = q.reshape(B, S, d.KV, G, d.hd)        # query head = kv * G + g
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(d.hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + linear(o.reshape(B, S, d.H * d.hd), w["wo"], precision)
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
        x = block(d, layer_weights(d, layer_keys[i]), x, precision)
    return np.asarray(head(d, k_emb, x, config["out_features"], precision))
