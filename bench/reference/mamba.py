"""Plain float32 reference of a Mamba-1 model over input embeddings, and the
first logits of its head at the last position.

A block, as Gu & Dao (arXiv:2312.00752) describe it: RMSNorm; an input
projection to (x, z); a causal depthwise convolution of width ``d_conv``
with bias, then SiLU; a projection of x to (dt, B, C); dt through its own
projection plus bias and softplus; A = -exp(A_log); the selective scan, run
one time step after another,

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,    y_t = C_t . h_t + D x_t,

gated by SiLU(z), an output projection, and the residual, kept in float32.
A final RMSNorm precedes the head.  Layers run one at a time, each drawing
its weights from the seed by the recipe in ``common``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import (draw, fan_in_std, head_weights, linear,
                              rms_norm, split_table)


class Dims(NamedTuple):
    D: int
    L: int
    di: int
    N: int
    K: int
    R: int
    rows: int
    eps: float
    dtype: str
    tie: bool


def dims(config: dict) -> Dims:
    return Dims(D=config["d_model"], L=config["n_layer"],
                di=config["d_inner"], N=config["d_state"],
                K=config["d_conv"], R=config["dt_rank"],
                rows=config["weights"]["embedding_rows"],
                eps=float(config["norm_eps"]),
                dtype=config["weights"]["dtype"],
                tie=bool(config.get("tie_embeddings", True)))


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(d: Dims, key) -> dict:
    dt = jnp.dtype(d.dtype)
    t = split_table(key, ["norm_ssm", "ssm"])
    s = split_table(t["ssm"], ["A_log", "D", "conv_b", "conv_w", "dt_bias",
                               "dt_proj", "in_proj", "out_proj", "x_proj"])

    def normal(k, shape, std=None):
        return draw(k, shape, fan_in_std(shape) if std is None else std, dt)

    return {
        "in_proj": normal(s["in_proj"], (d.D, 2 * d.di)),
        "conv_w": normal(s["conv_w"], (d.K, d.di), 0.5),
        "conv_b": jnp.zeros((d.di,), jnp.float32),
        "x_proj": normal(s["x_proj"], (d.di, d.R + 2 * d.N)),
        "dt_proj": normal(s["dt_proj"], (d.R, d.di)),
        "dt_bias": jnp.zeros((d.di,), jnp.float32),
        "A_log": jnp.ones((d.di, d.N), jnp.float32),
        "D": jnp.ones((d.di,), jnp.float32),
        "out_proj": normal(s["out_proj"], (d.di, d.D)),
        "norm": jnp.ones((d.D,), jnp.float32),
    }


@functools.partial(jax.jit, static_argnums=(0, 3))
def block(d: Dims, w: dict, x, precision: str):
    B, S, _ = x.shape
    h = rms_norm(x, w["norm"], d.eps)
    xz = linear(h, w["in_proj"], precision)
    xi, z = xz[..., :d.di], xz[..., d.di:]
    xp = jnp.concatenate([jnp.zeros((B, d.K - 1, d.di), jnp.float32), xi], 1)
    conv = sum(xp[:, i:i + S] * w["conv_w"][i] for i in range(d.K))
    xc = jax.nn.silu(conv + w["conv_b"])
    proj = linear(xc, w["x_proj"], precision)
    dt_in, Bt, Ct = (proj[..., :d.R], proj[..., d.R:d.R + d.N],
                     proj[..., d.R + d.N:])
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
    return x + linear(y, w["out_proj"], precision)


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
