"""Mamba-1 selective scan as a Pallas TPU kernel.

Grid = (batch, d_inner blocks, seq blocks), the seq dimension innermost
and sequential.  The state is held as (N, blk_d): d_inner on the lanes,
the N state entries on the sublanes, so a state of N = 16 fills two whole
vregs per 128 channels.  It lives in VMEM scratch across seq blocks (the
TPU-native replacement for the CUDA kernel's register-resident state) and
in registers within one: a ``fori_loop`` over the block's time steps,
:data:`GROUP` of them unrolled an iteration, each a handful of (N, blk_d)
VPU ops.  The scan is bound by those ops, not by HBM.

x and dt are read in the layout the model makes them, (batch, S, d_inner),
and y is written in it; a step reads one row of each.  B and C are read
transposed, (batch, N, S), so a step's N values are a column, which a lane
rotation brings to lane 0 and which is broadcast across the lanes.  Where
the whole sequence fits the VMEM budget it is one block, so S is never
padded; a longer sequence is cut into blocks of ``blk_s`` steps and the
last block runs only its valid steps.

Computes:  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
           y_t = (h_t * C_t).sum over N
and returns y and the last state (the D skip-connection and silu(z)
gating stay outside — see ``models/ssm.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import resolve_interpret

#: VMEM the double-buffered x, dt and y blocks may take
VMEM_BUDGET = 8 * 1024 * 1024

#: lane width: blocks of d_inner, and of a long sequence, are multiples
LANES = 128

#: named scope the ``pallas_call`` alone runs under (the layout changes of
#: its operands stay outside it)
SCOPE = "ssm_scan"

#: time steps a loop iteration unrolls (8 to 16 measured on a v5e: 16
#: spills); divides LANES, so a group's B and C columns lie in one tile
GROUP = 8


def block_sizes(S: int, di: int) -> tuple[int, int]:
    """(blk_d, blk_s) for a (S, d_inner) scan: the widest of 1024, 512, 256
    channels that divides d_inner (16 vregs of state at N = 16: the more
    channels a step holds, the more independent work the VPU has between
    one step's state and the next's), and the whole sequence where its x,
    dt and y blocks, double-buffered, fit :data:`VMEM_BUDGET`."""
    blk_d = next(b for b in (1024, 512, 256, LANES)
                 if di % b == 0 or b == LANES)
    whole = 3 * 2 * S * blk_d * 4
    if whole <= VMEM_BUDGET:
        return blk_d, S
    return blk_d, max(LANES, VMEM_BUDGET // (3 * 2 * blk_d * 4)
                      // LANES * LANES)


def _column(blk, lane):
    """(N, 1): lane ``lane`` of an (N, LANES) block (a dynamic lane slice
    is not Mosaic-legal; a lane rotation is)."""
    return pltpu.roll(blk, (LANES - lane) % LANES, 1)[:, :1]


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_ref, h_scr,
                 *, blk_s: int, seq_len: int):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...]                                     # (N, blk_d)
    steps = jnp.minimum(blk_s, seq_len - isq * blk_s)

    def update(h, dt, dtx, b, c):
        # dt, dtx: (1, blk_d) rows; b, c: (N, 1) columns
        h = jnp.exp(dt * a) * h + dtx * b              # (N, blk_d)
        return h, jnp.sum(h * c, axis=0, keepdims=True)

    def chunk_of(t):
        # the LANES steps of B and C around step t, one step a lane
        t0 = pl.multiple_of(t // LANES * LANES, LANES)
        return (t - t0, b_ref[0, :, pl.ds(t0, LANES)],
                c_ref[0, :, pl.ds(t0, LANES)])

    def group_body(gi, h):
        # GROUP steps unrolled: their rows come in one aligned tile, and
        # all but the state update are free of the previous step
        t = pl.multiple_of(gi * GROUP, GROUP)
        dt = dt_ref[0, pl.ds(t, GROUP), :]             # (GROUP, blk_d)
        dtx = dt * x_ref[0, pl.ds(t, GROUP), :]
        lane, b_blk, c_blk = chunk_of(t)
        for j in range(GROUP):
            h, y = update(h, dt[j:j + 1], dtx[j:j + 1],
                          _column(b_blk, lane + j), _column(c_blk, lane + j))
            y_ref[0, pl.ds(t + j, 1), :] = y
        return h

    def step_body(t, h):
        dt = dt_ref[0, pl.ds(t, 1), :]
        lane, b_blk, c_blk = chunk_of(t)
        h, y = update(h, dt, dt * x_ref[0, pl.ds(t, 1), :],
                      _column(b_blk, lane), _column(c_blk, lane))
        y_ref[0, pl.ds(t, 1), :] = y
        return h

    full = steps // GROUP
    h = jax.lax.fori_loop(0, full, group_body, h_scr[...])
    h = jax.lax.fori_loop(full * GROUP, steps, step_body, h)
    h_scr[...] = h

    @pl.when(isq == pl.num_programs(2) - 1)
    def _last():
        h_ref[0] = h


def selective_scan(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                   A: jax.Array, *, blk_d: Optional[int] = None,
                   blk_s: Optional[int] = None,
                   interpret: "bool | None" = None
                   ) -> tuple[jax.Array, jax.Array]:
    """x, dt: (batch, S, d_inner); B, C: (batch, S, N); A: (d_inner, N)
    (A already negative, i.e. ``A = -exp(A_log)``).  Returns y (batch, S,
    d_inner) and the last state (batch, d_inner, N), both f32.  Blocks
    default to :func:`block_sizes`; ``interpret=None`` resolves via
    :func:`repro.kernels.compat.resolve_interpret`."""
    auto_d, auto_s = block_sizes(x.shape[1], x.shape[2])
    return _selective_scan(x, dt, B, C, A, blk_d=blk_d or auto_d,
                           blk_s=blk_s or auto_s,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("blk_d", "blk_s", "interpret"))
def _selective_scan(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                    A: jax.Array, *, blk_d: int, blk_s: int,
                    interpret: bool) -> tuple[jax.Array, jax.Array]:
    bsz, S, di = x.shape
    N = A.shape[1]
    blk_d = min(blk_d, di)
    blk_s = min(blk_s, S)
    nd = -(-di // blk_d)
    ns = -(-S // blk_s)
    pad_d = nd * blk_d - di
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    a_t = A.astype(f32).T                               # (N, di)
    if pad_d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_d)))
        a_t = jnp.pad(a_t, ((0, 0), (0, pad_d)))
    # B, C: (batch, N, ns * blk_l), seq block s's steps at lanes
    # [s * blk_l, s * blk_l + blk_s), blk_l the block's steps rounded up to
    # whole lane tiles
    blk_l = -(-blk_s // LANES) * LANES

    def lanes_of_steps(a):
        a = jnp.pad(a.astype(f32), ((0, 0), (0, ns * blk_s - S), (0, 0)))
        a = a.reshape(bsz, ns, blk_s, N)
        a = jnp.pad(a, ((0, 0), (0, 0), (0, blk_l - blk_s), (0, 0)))
        return a.reshape(bsz, ns * blk_l, N).swapaxes(1, 2)

    b_t, c_t = lanes_of_steps(B), lanes_of_steps(C)

    with jax.named_scope(SCOPE):
        y, h = pl.pallas_call(
            functools.partial(_scan_kernel, blk_s=blk_s, seq_len=S),
            grid=(bsz, nd, ns),
            in_specs=[
                pl.BlockSpec((1, blk_s, blk_d), lambda b, d, s: (b, s, d)),
                pl.BlockSpec((1, blk_s, blk_d), lambda b, d, s: (b, s, d)),
                pl.BlockSpec((1, N, blk_l), lambda b, d, s: (b, 0, s)),
                pl.BlockSpec((1, N, blk_l), lambda b, d, s: (b, 0, s)),
                pl.BlockSpec((N, blk_d), lambda b, d, s: (0, d)),
            ],
            out_specs=[
                pl.BlockSpec((1, blk_s, blk_d), lambda b, d, s: (b, s, d)),
                pl.BlockSpec((1, N, blk_d), lambda b, d, s: (b, 0, d)),
            ],
            out_shape=[jax.ShapeDtypeStruct((bsz, S, nd * blk_d), f32),
                       jax.ShapeDtypeStruct((bsz, N, nd * blk_d), f32)],
            scratch_shapes=[pltpu.VMEM((N, blk_d), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(x, dt, b_t, c_t, a_t)                          # x, dt, B, C, A
    return y[..., :di], h[..., :di].swapaxes(1, 2)
