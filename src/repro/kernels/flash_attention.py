"""Fused GQA self-attention as a Pallas TPU kernel.

One program holds one row and one kv head, with that kv head's G = H / KV
query heads.  Scores, masking, softmax and × V stay in VMEM: the float32
scores never reach HBM.

Layout.  q, k and v are taken head-major, (B, H, S, hd) and
(B, KV, S, hd): the layout XLA gives the q/k norm and RoPE fusion and the
v projection on a TPU, so a caller's transposes into it are bitcasts.  The
output is written as (B, Sq, H·hd), which an output projection reads as
it stands; a program writes its G heads as the G·hd columns at column
block ``kv``.  On the chip those columns must be whole lane tiles
(:func:`lane_tiled`); interpret mode takes any shape.

Tiling follows the shape.  Where a grid step's blocks and one head's
float32 scores fit :data:`VMEM_BUDGET`, the blocks are the whole sequence
(grid (B, KV, 1, 1), one softmax per head, no padding).  Past it, q goes
in blocks of up to 512 rows and kv in blocks of :data:`KV_BLOCK`, swept
innermost with the online softmax (running max, sum and accumulator in
VMEM scratch); wholly masked kv blocks are skipped, and their index map
repeats a needed block so that they are not fetched either.

Precision: q·kᵀ takes the operands as given (bf16 in the model) with f32
accumulation; the mask, max, exp, sum and division are f32; p is cast to
v's dtype for p·v, which is what the MXU does with the f32 p of a
default-precision XLA dot.  Validated in interpret mode against
``ref.attention_reference`` and ``models.attention.chunked_attention``
(tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import resolve_interpret

NEG_INF = -1e30

#: the TPU's lane width: a block's last dim is a multiple of it, or whole
LANES = 128

#: VMEM one grid step may fill: double-buffered blocks, one head's f32
#: scores with their temporaries, and the sweep's scratch, under the
#: 16 MiB that Mosaic scopes for a kernel by default
VMEM_BUDGET = 12 << 20

#: the kv block of the sweep past the VMEM budget, which is also the most
#: q rows a sweep's block holds
KV_BLOCK = 512


def lane_tiled(num_heads: int, num_kv_heads: int, head_dim: int) -> bool:
    """Whether a program's output columns, G·hd of H·hd, are whole lane
    tiles, as the compiled kernel needs."""
    G = num_heads // num_kv_heads
    return num_kv_heads == 1 or (G * head_dim) % LANES == 0


def _step_bytes(blk_q: int, blk_k: int, G: int, hd: int, itemsize: int,
                sweep: bool) -> int:
    io = 2 * itemsize * (2 * blk_q * G * hd + 2 * blk_k * hd)
    scores = 3 * 4 * blk_q * blk_k
    scratch = 4 * blk_q * G * (hd + 2 * LANES) if sweep else 0
    return io + scores + scratch


def block_sizes(Sq: int, Sk: int, G: int, hd: int,
                itemsize: int) -> tuple[int, int]:
    """(blk_q, blk_k): the whole sequence where one grid step fits
    :data:`VMEM_BUDGET`, else kv blocks of :data:`KV_BLOCK` and the
    largest q block of 512, 256 or 128 rows that fits."""
    if _step_bytes(Sq, Sk, G, hd, itemsize, False) <= VMEM_BUDGET:
        return Sq, Sk
    blk_k = min(KV_BLOCK, Sk)
    for blk_q in (512, 256, 128):
        if _step_bytes(blk_q, blk_k, G, hd, itemsize, True) <= VMEM_BUDGET:
            break
    return min(blk_q, Sq), blk_k


def _mask(q_start, k_start, shape, *, causal, window, seq_k, padded):
    """(blk_q, blk_k) validity of each score, or None where all are."""
    if not (causal or window or padded):
        return None
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return mask


def _scores(q, k, scale, mask):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _pv(p, v):
    return jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _emit(o_ref, g, hd, acc, l):
    o_ref[0, :, g * hd:(g + 1) * hd] = (
        acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _whole_kernel(q_ref, k_ref, v_ref, o_ref, *, G, hd, scale, causal,
                  window, seq_k, q_offset):
    """The whole kv sequence in one block: one softmax per head."""
    k, v = k_ref[0, 0], v_ref[0, 0]                         # (Sk, hd)
    mask = _mask(q_offset, 0, (q_ref.shape[2], k.shape[0]), causal=causal,
                 window=window, seq_k=seq_k, padded=False)
    for g in range(G):
        s = _scores(q_ref[0, g], k, scale, mask)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        _emit(o_ref, g, hd, _pv(p, v), jnp.sum(p, axis=-1, keepdims=True))


def _kv_span(iq, *, blk_q, blk_k, causal, window, q_offset, nk):
    """First and last kv block any query of q block ``iq`` may see."""
    first, last = 0, nk - 1
    if causal:
        last = jnp.minimum(last, (iq * blk_q + blk_q - 1 + q_offset)
                           // blk_k)
    if window:
        first = jnp.maximum(first, (iq * blk_q + q_offset - window + 1)
                            // blk_k)
    return first, last


def _sweep_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  G, hd, scale, causal, window, seq_k, q_offset, padded):
    """kv swept in blocks with the online softmax."""
    blk_q, blk_k = q_ref.shape[2], k_ref.shape[2]
    iq, ik, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = _kv_span(iq, blk_q=blk_q, blk_k=blk_k, causal=causal,
                           window=window, q_offset=q_offset, nk=nk)

    @pl.when((ik >= first) & (ik <= last))
    def _compute():
        k, v = k_ref[0, 0], v_ref[0, 0]                     # (blk_k, hd)
        mask = _mask(iq * blk_q + q_offset, ik * blk_k, (blk_q, blk_k),
                     causal=causal, window=window, seq_k=seq_k,
                     padded=padded)
        for g in range(G):
            s = _scores(q_ref[0, g], k, scale, mask)
            m_prev = m_scr[g]                               # (blk_q, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[g] = m_new
            acc_scr[g] = acc_scr[g] * alpha[:, :1] + _pv(p, v)

    @pl.when(ik == nk - 1)
    def _done():
        for g in range(G):
            _emit(o_ref, g, hd, acc_scr[g], l_scr[g][:, :1])


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    blk_q: "int | None" = None, blk_k: "int | None" = None,
                    interpret: "bool | None" = None) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd), H % KV == 0, Sk >= Sq.

    Returns (B, Sq, H, hd) in q.dtype, queries at the last Sq of the Sk
    positions.  ``window`` > 0 adds sliding-window masking.  Blocks
    default to :func:`block_sizes`; ``blk_q``/``blk_k`` set them, and
    blocks smaller than the sequence sweep kv.  ``interpret=None``
    resolves via :func:`repro.kernels.compat.resolve_interpret`.
    """
    _, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    auto_q, auto_k = block_sizes(Sq, Sk, H // KV, hd, q.dtype.itemsize)
    return _flash_attention(q, k, v, causal=causal, window=window,
                            blk_q=min(blk_q or auto_q, Sq),
                            blk_k=min(blk_k or auto_k, Sk),
                            interpret=resolve_interpret(interpret))


def _pad_rows(x: jax.Array, blk: int) -> jax.Array:
    """(B, n, S, hd) -> (B, n, S', hd), S padded to a multiple of blk."""
    pad = -x.shape[2] % blk
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k",
                              "interpret"))
def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, window: int, blk_q: int, blk_k: int,
                     interpret: bool) -> jax.Array:
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    G = H // KV
    q_offset = Sk - Sq
    q = _pad_rows(q, blk_q)
    k, v = _pad_rows(k, blk_k), _pad_rows(v, blk_k)
    nq, nk = q.shape[2] // blk_q, k.shape[2] // blk_k
    common = dict(G=G, hd=hd, scale=hd ** -0.5, causal=causal,
                  window=window, seq_k=Sk, q_offset=q_offset)
    if nq == nk == 1:
        kernel = functools.partial(_whole_kernel, **common)
        scratch, semantics = [], ("parallel",) * 4
        kv_block = lambda b, h, iq, ik: (b, h, 0, 0)
    else:
        kernel = functools.partial(_sweep_kernel,
                                   padded=k.shape[2] > Sk, **common)
        scratch = [pltpu.VMEM((G, blk_q, LANES), jnp.float32),  # running max
                   pltpu.VMEM((G, blk_q, LANES), jnp.float32),  # running sum
                   pltpu.VMEM((G, blk_q, hd), jnp.float32)]     # accumulator
        semantics = ("parallel",) * 3 + ("arbitrary",)
        span = functools.partial(_kv_span, blk_q=blk_q, blk_k=blk_k,
                                 causal=causal, window=window,
                                 q_offset=q_offset, nk=nk)

        def kv_block(b, h, iq, ik):
            first, last = span(iq)
            return (b, h, jnp.clip(ik, first, last), 0)

    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nq, nk),
        in_specs=[pl.BlockSpec((1, G, blk_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
                  pl.BlockSpec((1, 1, blk_k, hd), kv_block),
                  pl.BlockSpec((1, 1, blk_k, hd), kv_block)],
        out_specs=pl.BlockSpec((1, blk_q, G * hd),
                               lambda b, h, iq, ik: (b, iq, h)),
        out_shape=jax.ShapeDtypeStruct((B, nq * blk_q, H * hd), q.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq].reshape(B, Sq, H, hd)
