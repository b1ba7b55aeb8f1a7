"""Flash attention as a Pallas TPU kernel.

Canonical TPU tiling: grid = (batch, q_heads, num_q_blocks, num_kv_blocks)
with the kv dimension innermost and sequential; the online-softmax running
max / sum / accumulator live in VMEM scratch that persists across the kv
sweep.  Causal masking skips fully-masked kv blocks (compute saved; the
BlockSpec prefetch still streams them).  GQA is handled in the k/v
index_map: q head h reads kv head ``h // (H // KV)``.

Block shapes are MXU-aligned (multiples of 128 on the lane dim).  Validated
in interpret mode against ``ref.attention_reference`` over shape/dtype
sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int,
                 blk_q: int, blk_k: int, seq_k: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * blk_q + q_offset      # absolute position of first query
    k_start = ik * blk_k

    # block-level skip: whole kv block masked => no compute (flops saved)
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + blk_q - 1
    if window:
        run &= k_start + blk_k - 1 > q_start - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (blk_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (blk_k, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (blk_q, blk_k)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # (blk_q, 128)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # (blk_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)             # lanes replicated
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                  # (blk_q, blk_k)
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: "bool | None" = None) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd); H % KV == 0.

    Returns (B, H, Sq, hd) in q.dtype.  ``window`` > 0 adds sliding-window
    masking on top of causal.  ``interpret=None`` resolves via
    :func:`repro.kernels.compat.resolve_interpret`.
    """
    return _flash_attention(q, k, v, causal=causal, window=window,
                            blk_q=blk_q, blk_k=blk_k,
                            interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k",
                              "interpret"))
def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, window: int, blk_q: int, blk_k: int,
                     interpret: bool) -> jax.Array:
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    G = H // KV
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    nq = -(-Sq // blk_q)
    nk = -(-Sk // blk_k)
    pad_q = nq * blk_q - Sq
    pad_k = nk * blk_k - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=hd ** -0.5, causal=causal,
                          window=window, blk_q=blk_q, blk_k=blk_k,
                          seq_k=Sk, q_offset=Sk - Sq),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * blk_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((blk_q, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :Sq]
    return out
