"""Mamba-1 selective SSM (falcon-mamba-7b; the SSM half of hymba; the
Mamba layers of Jamba, whose mixer adds RMSNorms on dt, B and C:
``ModelConfig.ssm_inner_norms``).

The jnp sequence path uses a *chunked* associative scan: an outer
``lax.scan`` over time blocks carries the (B, d_inner, N) state, an inner
``lax.associative_scan`` parallelises within the block.  This bounds
activation memory to O(block) instead of O(S) — required for the
prefill_32k / long_500k cells — while keeping the parallel-scan depth the
TPU likes.  :func:`ssm_forward` runs the Pallas kernel of
``repro.kernels.selective_scan`` in its place where that kernel is compiled
for a TPU (``kernels.compat.resolve_interpret(None)`` is False) and no mesh
over more than one device is active, as ``attention.gqa_forward`` does for
its kernel; its gradient is the VJP of the jnp path.  Either scan runs
under the ``ssm_scan`` scope, and the ``ssm`` metrics scope counts each
trace of either path (counters ``kernel``, ``jnp``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed import context as ctx
from repro.kernels.compat import resolve_interpret
from repro.kernels.selective_scan import SCOPE, selective_scan
from repro.obs import metrics as obs_metrics

from .config import ModelConfig
from .layers import ParamDef, rms_norm

#: which scan each trace of :func:`ssm_forward` took
_PATHS = obs_metrics.scope("ssm")


class SSMCache(NamedTuple):
    conv: jax.Array    # (B, K-1, d_inner) — last K-1 pre-conv inputs
    state: jax.Array   # (B, d_inner, N) — SSM hidden state


def ssm_table(cfg: ModelConfig) -> dict[str, ParamDef]:
    D, di = cfg.d_model, cfg.ssm_d_inner
    N, K, R = cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    t = {
        "in_proj": ParamDef((D, 2 * di), ("embed", "ssm_inner")),
        "conv_w": ParamDef((K, di), (None, "ssm_inner"), scale=0.5),
        "conv_b": ParamDef((di,), ("ssm_inner",), init="zeros"),
        "x_proj": ParamDef((di, R + 2 * N), ("ssm_inner", None)),
        "dt_proj": ParamDef((R, di), (None, "ssm_inner")),
        "dt_bias": ParamDef((di,), ("ssm_inner",), init=(
            "dt_bias" if cfg.ssm_init == "mamba" else "zeros")),
        "A_log": ParamDef((di, N), ("ssm_inner", None), init=(
            "s4d_real" if cfg.ssm_init == "mamba" else "ones")),
        "D": ParamDef((di,), ("ssm_inner",), init="ones"),
        "out_proj": ParamDef((di, D), ("ssm_inner", "embed")),
    }
    if cfg.ssm_inner_norms:
        t["dt_norm"] = ParamDef((R,), (None,), init="ones")
        t["b_norm"] = ParamDef((N,), (None,), init="ones")
        t["c_norm"] = ParamDef((N,), (None,), init="ones")
    return t


def _ssm_coeffs(cfg: ModelConfig, p: dict, xc: jax.Array):
    """xc: (B, S, di) post-conv activations -> dt, B_t, C_t (f32)."""
    R, N = cfg.ssm_dt_rank, cfg.ssm_state
    proj = jnp.einsum("bsd,dr->bsr", xc, p["x_proj"].astype(xc.dtype))
    dt, Bt, Ct = jnp.split(proj.astype(jnp.float32), [R, R + N], axis=-1)
    if cfg.ssm_inner_norms:
        dt = rms_norm(dt, p["dt_norm"], cfg.norm_eps)
        Bt = rms_norm(Bt, p["b_norm"], cfg.norm_eps)
        Ct = rms_norm(Ct, p["c_norm"], cfg.norm_eps)
    dt = jnp.einsum("bsr,rd->bsd", dt, p["dt_proj"].astype(jnp.float32))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return dt, Bt, Ct


def _causal_conv(cfg: ModelConfig, p: dict, x: jax.Array,
                 left_ctx: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv1d. x: (B, S, di). left_ctx: (B, K-1, di)."""
    K = cfg.ssm_conv
    if left_ctx is None:
        left_ctx = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([left_ctx, x], axis=1)          # (B, S+K-1, di)
    w = p["conv_w"].astype(x.dtype)                      # (K, di)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    return out + p["conv_b"].astype(x.dtype)


def _chunked_scan(xc: jax.Array, dt: jax.Array, Bt: jax.Array,
                  Ct: jax.Array, A: jax.Array, block: int,
                  scan_dt=jnp.float32, unroll: bool = False
                  ) -> tuple[jax.Array, jax.Array]:
    """The jnp scan: (y (B, S, di) f32, last state (B, di, N) f32)."""
    B, S, di = xc.shape
    N = A.shape[1]
    nb = -(-S // block)
    pad = nb * block - S
    if pad:
        padded = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        xc_, dt_, Bt_, Ct_ = map(padded, (xc, dt, Bt, Ct))
    else:
        xc_, dt_, Bt_, Ct_ = xc, dt, Bt, Ct

    def blockify(a):
        return jnp.moveaxis(a.reshape(B, nb, block, -1), 1, 0)

    xb, dtb, Btb, Ctb = map(blockify, (xc_, dt_, Bt_, Ct_))

    def block_step(h, inp):
        xj, dtj, Bj, Cj = inp                             # (B, blk, ·)
        # a_t = exp(dt_t A): (B, blk, di, N); b_t = dt_t * B_t * x_t
        a = jnp.exp(dtj[..., None] * A).astype(scan_dt)   # (B, blk, di, N)
        a = ctx.constrain(a, ctx.dp(), None, "model", None)
        b = ((dtj * xj.astype(jnp.float32))[..., None]
             * Bj[:, :, None, :]).astype(scan_dt)
        b = ctx.constrain(b, ctx.dp(), None, "model", None)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        a_cum, b_cum = jax.lax.associative_scan(combine, (a, b), axis=1)
        hs = a_cum.astype(jnp.float32) * h[:, None] \
            + b_cum.astype(jnp.float32)                   # (B, blk, di, N)
        y = jnp.einsum("bsdn,bsn->bsd", hs.astype(scan_dt),
                       Cj.astype(scan_dt)).astype(jnp.float32)
        return hs[:, -1], y

    h0 = jnp.zeros((B, di, N), jnp.float32)
    if unroll:
        h = h0
        ys = []
        for j in range(nb):
            h, yj = block_step(h, (xb[j], dtb[j], Btb[j], Ctb[j]))
            ys.append(yj)
        h_last, yb = h, jnp.stack(ys)
    else:
        h_last, yb = jax.lax.scan(block_step, h0, (xb, dtb, Btb, Ctb))
    y = jnp.moveaxis(yb, 0, 1).reshape(B, nb * block, di)[:, :S]
    return y, h_last


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kernel_scan(xc: jax.Array, dt: jax.Array, Bt: jax.Array, Ct: jax.Array,
                A: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """The Pallas scan (it runs under the ``ssm_scan`` scope); its gradient
    is that of :func:`_chunked_scan` with ``block``, recomputed from the
    inputs."""
    return selective_scan(xc, dt, Bt, Ct, A)


def _kernel_scan_fwd(xc, dt, Bt, Ct, A, block):
    return kernel_scan(xc, dt, Bt, Ct, A, block), (xc, dt, Bt, Ct, A)


def _kernel_scan_bwd(block, res, g):
    _, vjp = jax.vjp(functools.partial(_chunked_scan, block=block), *res)
    return vjp(g)


kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def _takes_kernel() -> bool:
    """Whether :func:`ssm_forward` runs the Pallas scan: compiled for a
    TPU, on one device (a ``pallas_call`` is not partitioned)."""
    mesh = ctx.mesh_ctx()
    return (not resolve_interpret(None)
            and (mesh is None or mesh.devices.size == 1))


def ssm_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                block: int = 0) -> tuple[jax.Array, SSMCache]:
    """Full-sequence selective scan. x: (B, S, D) -> (B, S, D).

    Returns the final SSMCache so prefill can hand off to decode.
    """
    B, S, D = x.shape
    if block <= 0:
        block = cfg.ssm_block if cfg.ssm_block > 0 else S
        block = min(block, S)
    di, K = cfg.ssm_d_inner, cfg.ssm_conv
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    xin, z = jnp.split(xz, 2, axis=-1)                    # (B, S, di) each
    # d_inner-sharded activations (matches the ssm_inner weight sharding):
    # the (B, blk, di, N) scan intermediates are 16*N x the residual size,
    # so leaving di unsharded melts HBM at the 32k/500k cells
    xin = ctx.constrain(xin, ctx.dp(), None, "model")
    z = ctx.constrain(z, ctx.dp(), None, "model")
    xc = jax.nn.silu(_causal_conv(cfg, p, xin))
    xc = ctx.constrain(xc, ctx.dp(), None, "model")
    dt, Bt, Ct = _ssm_coeffs(cfg, p, xc)
    dt = ctx.constrain(dt, ctx.dp(), None, "model")
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (di, N)

    if _takes_kernel():
        _PATHS.counter("kernel").inc()
        y, h_last = kernel_scan(xc, dt, Bt, Ct, A, block)
    else:
        _PATHS.counter("jnp").inc()
        with jax.named_scope(SCOPE):
            y, h_last = _chunked_scan(
                xc, dt, Bt, Ct, A, block,
                jnp.bfloat16 if cfg.ssm_bf16 else jnp.float32,
                cfg.unroll_inner)
    y = ctx.constrain(y, ctx.dp(), None, "model")
    y = y + xc.astype(jnp.float32) * p["D"].astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"].astype(x.dtype))
    conv_tail = jnp.concatenate(
        [jnp.zeros((B, K - 1, di), x.dtype), xin], axis=1)[:, -(K - 1):]
    return out, SSMCache(conv_tail, h_last)


def ssm_decode(cfg: ModelConfig, p: dict, x: jax.Array,
               cache: SSMCache) -> tuple[jax.Array, SSMCache]:
    """One-token recurrent step. x: (B, 1, D)."""
    B = x.shape[0]
    di, N, K = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_conv
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    xin, z = jnp.split(xz, 2, axis=-1)                    # (B, 1, di)
    window = jnp.concatenate([cache.conv, xin], axis=1)   # (B, K, di)
    w = p["conv_w"].astype(x.dtype)
    xc = jax.nn.silu(jnp.einsum("bkd,kd->bd", window, w)
                     + p["conv_b"].astype(x.dtype))[:, None, :]
    dt, Bt, Ct = _ssm_coeffs(cfg, p, xc)                  # (B, 1, ·)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt[..., None] * A)[:, 0]                  # (B, di, N)
    b = ((dt * xc.astype(jnp.float32))[..., None]
         * Bt[:, :, None, :])[:, 0]                       # (B, di, N)
    h = cache.state * a + b
    y = jnp.einsum("bdn,bn->bd", h, Ct[:, 0])
    y = y + xc[:, 0].astype(jnp.float32) * p["D"].astype(jnp.float32)
    y = (y.astype(x.dtype) * jax.nn.silu(z[:, 0]))[:, None, :]
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"].astype(x.dtype))
    return out, SSMCache(window[:, 1:], h)


def ssm_empty_cache(cfg: ModelConfig, batch: int, dtype) -> SSMCache:
    return SSMCache(
        jnp.zeros((batch, cfg.ssm_conv - 1, cfg.ssm_d_inner), dtype),
        jnp.zeros((batch, cfg.ssm_d_inner, cfg.ssm_state), jnp.float32))
