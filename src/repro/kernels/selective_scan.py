"""Mamba-1 selective scan as a Pallas TPU kernel.

Grid = (batch, d_inner blocks, seq blocks) with the seq dimension innermost
and sequential; the SSM hidden state (blk_d, N) lives in VMEM scratch and is
carried across seq blocks — the TPU-native replacement for the CUDA
kernel's register-resident state.  Within a block the recurrence runs as a
``fori_loop`` over time steps; channels are vectorised across lanes (blk_d
is lane-aligned at 128) so each step is a (blk_d, N) VPU op, not a scalar
loop.

Computes:  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
           y_t = (h_t * C_t).sum(-1)
(the D skip-connection and silu(z) gating stay outside — see ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import resolve_interpret


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *,
                 blk_s: int):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_neg = a_ref[...].astype(jnp.float32)             # (blk_d, N)

    def step(t, h):
        dt = dt_ref[0, t, :].astype(jnp.float32)       # (blk_d,)
        xt = x_ref[0, t, :].astype(jnp.float32)        # (blk_d,)
        bt = b_ref[0, t, :].astype(jnp.float32)        # (N,)
        ct = c_ref[0, t, :].astype(jnp.float32)        # (N,)
        decay = jnp.exp(dt[:, None] * a_neg)           # (blk_d, N)
        h = decay * h + (dt * xt)[:, None] * bt[None, :]
        y_ref[0, t, :] = (h * ct[None, :]).sum(-1).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, blk_s, step, h_scr[...])


def selective_scan(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                   A: jax.Array, *, blk_d: int = 128, blk_s: int = 128,
                   interpret: "bool | None" = None) -> jax.Array:
    """x, dt: (batch, S, d_inner); B, C: (batch, S, N); A: (d_inner, N)
    (A already negative, i.e. ``A = -exp(A_log)``).  Returns y (batch, S,
    d_inner) f32.  ``interpret=None`` resolves via
    :func:`repro.kernels.compat.resolve_interpret`."""
    return _selective_scan(x, dt, B, C, A, blk_d=blk_d, blk_s=blk_s,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("blk_d", "blk_s", "interpret"))
def _selective_scan(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                    A: jax.Array, *, blk_d: int, blk_s: int,
                    interpret: bool) -> jax.Array:
    bsz, S, di = x.shape
    N = A.shape[1]
    blk_d = min(blk_d, di)
    blk_s = min(blk_s, S)
    nd = -(-di // blk_d)
    ns = -(-S // blk_s)
    pad_d = nd * blk_d - di
    pad_s = ns * blk_s - S
    if pad_d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_d)))
        A = jnp.pad(A, ((0, pad_d), (0, 0)))
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_s), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad_s), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad_s), (0, 0)))

    y = pl.pallas_call(
        functools.partial(_scan_kernel, blk_s=blk_s),
        grid=(bsz, nd, ns),
        in_specs=[
            pl.BlockSpec((1, blk_s, blk_d), lambda b, d, s: (b, s, d)),  # x
            pl.BlockSpec((1, blk_s, blk_d), lambda b, d, s: (b, s, d)),  # dt
            pl.BlockSpec((1, blk_s, N), lambda b, d, s: (b, s, 0)),      # B
            pl.BlockSpec((1, blk_s, N), lambda b, d, s: (b, s, 0)),      # C
            pl.BlockSpec((blk_d, N), lambda b, d, s: (d, 0)),            # A
        ],
        out_specs=pl.BlockSpec((1, blk_s, blk_d), lambda b, d, s: (b, s, d)),
        out_shape=jax.ShapeDtypeStruct((bsz, ns * blk_s, nd * blk_d),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, B, C, A)
    return y[:, :S, :di]
