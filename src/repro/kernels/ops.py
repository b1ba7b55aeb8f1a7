"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves through
:func:`repro.kernels.compat.resolve_interpret`: the ``REPRO_PALLAS_INTERPRET``
env var wins, otherwise compiled Mosaic on a real TPU backend and Python
interpret mode everywhere else (this container is CPU-only; the kernels are
*targeted* at TPU and validated in interpret mode).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .compat import resolve_interpret
from .flash_attention import flash_attention
from .selective_scan import selective_scan
from .sensor_decode import sensor_decode


def _interpret_default() -> bool:
    # kept for callers that need the resolved mode itself (benchmarks)
    return resolve_interpret(None)


def attention(q, k, v, *, causal=True, window=0, blk_q=None, blk_k=None,
              interpret=None):
    """Flash attention; layout (B, H, S, hd) / (B, KV, S, hd).  Blocks
    default to the kernel's
    :func:`~repro.kernels.flash_attention.block_sizes`."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           blk_q=blk_q, blk_k=blk_k,
                           interpret=interpret).swapaxes(1, 2)


def mamba_scan(x, dt, B, C, A, *, blk_d=None, blk_s=None, interpret=None):
    """Selective scan's y; x/dt (b,S,di), B/C (b,S,N), A (di,N) negative.
    Blocks default to the kernel's
    :func:`~repro.kernels.selective_scan.block_sizes`."""
    return selective_scan(x, dt, B, C, A, blk_d=blk_d, blk_s=blk_s,
                          interpret=interpret)[0]


def decode_records(payload, scale, zero_point, lengths, *, blk_r=8,
                   blk_n=512, interpret=None):
    """On-device BinPipedRDD decode stage (paper Fig 4)."""
    return sensor_decode(payload, scale, zero_point, lengths,
                         blk_r=blk_r, blk_n=blk_n, interpret=interpret)


def decode_partition(partition, feature_bytes: int, *, interpret=None):
    """Convenience: core.binpipe.BinaryPartition -> (R, feature_bytes) f32
    feature matrix on device (frame + pad/clip + dequantize)."""
    payload, offsets, lengths = partition.to_arrays(align=128)
    R = len(lengths)
    rows = np.zeros((R, feature_bytes), np.uint8)
    for i, (o, l) in enumerate(zip(offsets.tolist(), lengths.tolist())):
        n = min(l, feature_bytes)
        rows[i, :n] = payload[o:o + n]
    lengths = np.minimum(lengths, feature_bytes).astype(np.int32)
    scale = np.full((R,), 1.0 / 255.0, np.float32)
    zp = np.zeros((R,), np.float32)
    return decode_records(jnp.asarray(rows), jnp.asarray(scale),
                          jnp.asarray(zp), jnp.asarray(lengths),
                          interpret=interpret)
