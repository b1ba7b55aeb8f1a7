"""Mamba-1 selective SSM (falcon-mamba-7b; the SSM half of hymba; the
Mamba layers of Jamba, whose mixer adds RMSNorms on dt, B and C:
``ModelConfig.ssm_inner_norms``).

The jnp sequence path uses a *chunked* associative scan: an outer
``lax.scan`` over time blocks carries the (B, d_inner, N) state, an inner
``lax.associative_scan`` parallelises within the block.  This bounds
activation memory to O(block) instead of O(S) while keeping the
parallel-scan depth the TPU likes.  :func:`ssm_forward` runs the Pallas
kernel of ``repro.kernels.selective_scan`` in its place where that kernel
is compiled for a TPU (``kernels.compat.compiled_for_tpu``), as
``attention.gqa_forward`` does for its kernel; its gradient is the VJP of
the jnp path.  Either scan runs under the ``ssm_scan`` scope, and the
``ssm`` metrics scope counts each trace of either path (counters
``kernel``, ``jnp``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import compat
from repro.kernels.selective_scan import SCOPE, selective_scan
from repro.obs import metrics as obs_metrics

from .config import ModelConfig
from .layers import ParamDef, rms_norm

#: which scan each trace of :func:`ssm_forward` took
_PATHS = obs_metrics.scope("ssm")


def ssm_table(cfg: ModelConfig) -> dict[str, ParamDef]:
    D, di = cfg.d_model, cfg.ssm_d_inner
    N, K, R = cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    t = {
        "in_proj": ParamDef((D, 2 * di)),
        "conv_w": ParamDef((K, di), scale=0.5),
        "conv_b": ParamDef((di,), init="zeros"),
        "x_proj": ParamDef((di, R + 2 * N)),
        "dt_proj": ParamDef((R, di)),
        "dt_bias": ParamDef((di,), init=(
            "dt_bias" if cfg.ssm_init == "mamba" else "zeros")),
        "A_log": ParamDef((di, N), init=(
            "s4d_real" if cfg.ssm_init == "mamba" else "ones")),
        "D": ParamDef((di,), init="ones"),
        "out_proj": ParamDef((di, D)),
    }
    if cfg.ssm_inner_norms:
        t["dt_norm"] = ParamDef((R,), init="ones")
        t["b_norm"] = ParamDef((N,), init="ones")
        t["c_norm"] = ParamDef((N,), init="ones")
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


def _causal_conv(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. x: (B, S, di)."""
    K = cfg.ssm_conv
    left_ctx = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([left_ctx, x], axis=1)          # (B, S+K-1, di)
    w = p["conv_w"].astype(x.dtype)                      # (K, di)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    return out + p["conv_b"].astype(x.dtype)


def _chunked_scan(xc: jax.Array, dt: jax.Array, Bt: jax.Array,
                  Ct: jax.Array, A: jax.Array, block: int
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
        a = jnp.exp(dtj[..., None] * A)                   # (B, blk, di, N)
        b = (dtj * xj.astype(jnp.float32))[..., None] * Bj[:, :, None, :]

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        a_cum, b_cum = jax.lax.associative_scan(combine, (a, b), axis=1)
        hs = a_cum * h[:, None] + b_cum                   # (B, blk, di, N)
        return hs[:, -1], jnp.einsum("bsdn,bsn->bsd", hs, Cj)

    h0 = jnp.zeros((B, di, N), jnp.float32)
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


def ssm_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                block: int = 0) -> jax.Array:
    """Full-sequence selective scan. x: (B, S, D) -> (B, S, D)."""
    S = x.shape[1]
    if block <= 0:
        block = cfg.ssm_block if cfg.ssm_block > 0 else S
        block = min(block, S)
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    xin, z = jnp.split(xz, 2, axis=-1)                    # (B, S, di) each
    xc = jax.nn.silu(_causal_conv(cfg, p, xin))
    dt, Bt, Ct = _ssm_coeffs(cfg, p, xc)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (di, N)

    if compat.compiled_for_tpu():
        _PATHS.counter("kernel").inc()
        y, _ = kernel_scan(xc, dt, Bt, Ct, A, block)
    else:
        _PATHS.counter("jnp").inc()
        with jax.named_scope(SCOPE):
            y, _ = _chunked_scan(xc, dt, Bt, Ct, A, block)
    y = y + xc.astype(jnp.float32) * p["D"].astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    return jnp.einsum("bsd,de->bse", y, p["out_proj"].astype(x.dtype))
