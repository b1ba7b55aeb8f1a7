"""Shared layers and the declarative parameter-table mechanism.

Every block declares its parameters once as ``name -> ParamDef(shape,
init)``, and ``init_table`` draws them in sorted name order.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ParamDef:
    shape: tuple
    init: str = "normal"              # normal | zeros | ones | s4d_real
                                      # | dt_bias
    scale: Optional[float] = None     # stddev override


def init_table(key: jax.Array, table: dict[str, ParamDef],
               dtype=jnp.float32) -> dict[str, jax.Array]:
    keys = jax.random.split(key, len(table))
    out = {}
    for (name, pd), k in zip(sorted(table.items()), keys):
        if pd.init == "zeros":
            out[name] = jnp.zeros(pd.shape, dtype)
        elif pd.init == "ones":
            out[name] = jnp.ones(pd.shape, dtype)
        elif pd.init == "s4d_real":
            # A_log = log(1..N) along the last axis: A = -(1..N)
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, pd.shape[-1] + 1, dtype=jnp.float32)), pd.shape).astype(
                    dtype)
        elif pd.init == "dt_bias":
            out[name] = dt_bias(k, pd.shape).astype(dtype)
        else:
            fan_in = pd.shape[0] if len(pd.shape) >= 2 else pd.shape[-1]
            if len(pd.shape) == 3:    # stacked expert weights: (E, in, out)
                fan_in = pd.shape[1]
            std = pd.scale if pd.scale is not None else 1.0 / math.sqrt(fan_in)
            out[name] = (jax.random.normal(k, pd.shape, jnp.float32)
                         * std).astype(dtype)
    return out


#: Mamba's initial step sizes: log-uniform on [DT_MIN, DT_MAX], floored
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def dt_bias(key: jax.Array, shape: tuple) -> jax.Array:
    """Mamba's (Gu & Dao, arXiv:2312.00752) initial dt bias, float32: the
    inverse softplus of a step size drawn log-uniform on [DT_MIN, DT_MAX]
    and floored at DT_FLOOR."""
    lo, hi = math.log(DT_MIN), math.log(DT_MAX)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo)
                 + lo)
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


# --------------------------------------------------------------------------
# normalisation
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# rotary embeddings (RoPE + M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S) int32."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                            # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., S, hd/2)
    ang = ang[..., None, :]                                # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: tuple) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, hd);  positions3: (B, S, 3) — (temporal, height, width)
    position ids.  ``sections`` partitions the hd/2 frequency slots among the
    three axes (e.g. (16, 24, 24) for hd=128).
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    inv = rope_freqs(hd, theta)                            # (hd/2,)
    # pick which position axis drives each frequency slot
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections),
                        total_repeat_length=hd // 2)       # (hd/2,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                    # (B, S, 3)
        jnp.broadcast_to(sec_id, positions3.shape[:-1] + (hd // 2,)).astype(
            jnp.int32),
        axis=-1)                                           # (B, S, hd/2)
    ang = (pos * inv)[..., None, :]                        # (B, S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def mlp_table(d_model: int, d_ff: int) -> dict[str, ParamDef]:
    return {
        "w_gate": ParamDef((d_model, d_ff)),
        "w_up": ParamDef((d_model, d_ff)),
        "w_down": ParamDef((d_ff, d_model)),
    }


def mlp_forward(p: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = _activate(h, act) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


def _activate(x: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x)
    if act == "relu":
        return jax.nn.relu(x)
    raise ValueError(act)


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------

def embed_table(vocab: int, d_model: int, tie: bool) -> dict[str, ParamDef]:
    t = {
        "embedding": ParamDef((vocab, d_model), scale=1.0),
        "final_norm": ParamDef((d_model,), init="ones"),
    }
    if not tie:
        t["lm_head"] = ParamDef((d_model, vocab))
    return t


def embed_tokens(p: dict, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(dtype)


def lm_logits(p: dict, x: jax.Array, tie: bool) -> jax.Array:
    if tie:
        w = p["embedding"].astype(x.dtype)
        return jnp.einsum("bsd,vd->bsv", x, w)
    return jnp.einsum("bsd,dv->bsv", x, p["lm_head"].astype(x.dtype))
