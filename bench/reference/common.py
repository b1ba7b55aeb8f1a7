"""Pieces the plain references share: the weight recipe, the decode of a
record into features, RMSNorm, and the one matrix product every linear layer
goes through.

The references import nothing of the program.  Their weights are drawn from
the seed by the recipe the configuration files state (``weights`` there):
a key is split into one key per table, tables and their entries in sorted
name order; a ``normal`` entry is ``N(0, 1) * std`` drawn in float32 and
stored in the served dtype; ``ones`` and ``zeros`` are constants.  The
reference reads the stored values back as float32 and computes in float32
at ``highest`` matmul precision.

``precision`` selects the control: ``"float32"`` is the reference itself;
``"fp8"`` rounds both operands of every linear layer to float8 e4m3, scaled
per row of the activations and per output column of the weights, a step
below the bfloat16 the configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "fp8")


def split_table(key, names):
    """{name: key} over ``names`` in sorted order, one split of ``key``."""
    names = sorted(names)
    keys = jax.random.split(key, len(names))
    return {n: keys[i] for i, n in enumerate(names)}


def draw(key, shape, std, dtype):
    """One ``normal`` entry of the recipe, as float32 values of ``dtype``."""
    w = jax.random.normal(key, shape, jnp.float32) * std
    return w.astype(dtype).astype(jnp.float32)


def fan_in_std(shape) -> float:
    return 1.0 / math.sqrt(shape[0])


def decode_features(payload: np.ndarray, d_model: int) -> np.ndarray:
    """A record's bytes as the model's input embeddings: each byte ``b`` is
    ``b / 255``; the record is cut into ``len // d_model`` tokens after
    padding to a multiple of 128 bytes (the frame's lane width), as the
    system's decode contract states.  Returns (tokens, d_model) float32."""
    nb = -(-len(payload) // 128) * 128
    s = nb // d_model
    x = np.zeros(nb, np.float32)
    x[:len(payload)] = payload.astype(np.float32) * np.float32(1.0 / 255.0)
    return x[:s * d_model].reshape(s, d_model)


def head_weights(key, rows: int, d_model: int, tie: bool, dtype,
                 out_features: int):
    """(d_model, out_features) output projection: the tied embedding's
    first rows, or the first columns of an untied ``lm_head``."""
    names = ["embedding", "final_norm"] + ([] if tie else ["lm_head"])
    k = split_table(key, names)
    if tie:
        return draw(k["embedding"], (rows, d_model), 1.0,
                    dtype)[:out_features].T
    return draw(k["lm_head"], (d_model, rows), fan_in_std((d_model,)),
                dtype)[:, :out_features]


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _fp8(a, axis):
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x, w, precision: str):
    """x (..., d_in) @ w (d_in, d_out) at the reference's precision."""
    if precision == "float32":
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return jnp.matmul(_fp8(x, -1), _fp8(w, 0),
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")
