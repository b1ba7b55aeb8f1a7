"""Unit tests for the numeric building blocks: chunked attention vs naive
softmax, MoE dispatch vs dense oracle, SSM scan vs recurrence, M-RoPE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.layers import apply_mrope, apply_rope, init_table


def naive_attention(q, k, v, causal=True, window=0):
    B, Sq, H, hd = q.shape
    _, Sk, KV, vd = v.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                   k.astype(jnp.float32)) * hd ** -0.5
    qpos = jnp.arange(Sq) + (Sk - Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None] <= qpos[:, None]
    if window:
        mask &= kpos[None] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", w, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, vd)


@pytest.mark.parametrize("Sq,Sk,H,KV,window,chunk", [
    (16, 16, 4, 4, 0, 8),
    (32, 32, 8, 2, 0, 8),
    (32, 32, 4, 1, 12, 16),
    (8, 24, 4, 2, 0, 7),       # cross-size + non-divisible chunk
    (33, 33, 4, 2, 0, 8),      # ragged
])
def test_chunked_attention_matches_naive(Sq, Sk, H, KV, window, chunk):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    B, hd = 2, 16
    q = jax.random.normal(kq, (B, Sq, H, hd))
    k = jax.random.normal(kk, (B, Sk, KV, hd))
    v = jax.random.normal(kv, (B, Sk, KV, hd))
    got = A.chunked_attention(q, k, v, causal=True, window=window,
                              chunk=chunk)
    want = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunked_attention_noncausal():
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (2, 8, 4, 16))
    k = jax.random.normal(key, (2, 40, 4, 16))
    v = jax.random.normal(key, (2, 40, 4, 16))
    got = A.chunked_attention(q, k, v, causal=False, chunk=16)
    want = naive_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("compiled,head_dim,path", [
    (False, 128, "chunked"),        # CPU: kernels interpret
    (True, 128, "fused"),           # as on a TPU
    (True, 16, "chunked"),          # a kv head's queries under a lane tile
])
def test_gqa_forward_dispatch(monkeypatch, compiled, head_dim, path):
    cfg = tiny_config("qwen3-4b").replace(num_kv_heads=2, head_dim=head_dim)
    p = init_table(jax.random.PRNGKey(0), A.gqa_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    want, _ = A.gqa_forward(cfg, p, x, pos)
    counters = {n: A._PATHS.counter(n) for n in ("fused", "chunked")}
    before = {n: c.value for n, c in counters.items()}
    # only the dispatch sees a compiled backend: the kernel itself still
    # resolves to interpret mode here
    monkeypatch.setattr(A, "resolve_interpret", lambda _: not compiled)
    got, kv = A.gqa_forward(cfg, p, x, pos)
    assert {n: c.value - before[n] for n, c in counters.items()} == {
        n: int(n == path) for n in counters}
    assert kv.k.shape == (2, 24, cfg.num_kv_heads, head_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_moe_dispatch_matches_dense_oracle():
    """With capacity >> tokens nothing drops, so scatter dispatch must equal
    the dense run-every-expert oracle exactly."""
    cfg = tiny_config("granite-moe-1b-a400m").replace(
        moe_capacity_factor=64.0)   # no drops
    key = jax.random.PRNGKey(0)
    p = init_table(key, MOE.moe_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    got = MOE.moe_forward(cfg, p, x)
    want = MOE.moe_forward_dense_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_bounded():
    """With tight capacity some tokens drop, but output stays finite and
    dropped tokens contribute zero (residual carries them)."""
    cfg = tiny_config("granite-moe-1b-a400m").replace(
        moe_capacity_factor=0.5)
    p = init_table(jax.random.PRNGKey(0), MOE.moe_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    out = MOE.moe_forward(cfg, p, x)
    assert bool(jnp.isfinite(out).all())


def test_ssm_scan_matches_stepwise_decode():
    """Chunked associative scan == token-by-token recurrence."""
    cfg = tiny_config("falcon-mamba-7b")
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    B, S = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.5
    y_scan, final = SSM.ssm_forward(cfg, p, x, block=8)

    cache = SSM.ssm_empty_cache(cfg, B, jnp.float32)
    ys = []
    for t in range(S):
        yt, cache = SSM.ssm_decode(cfg, p, x[:, t:t + 1], cache)
        ys.append(yt[:, 0])
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_step),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final.state),
                               np.asarray(cache.state), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final.conv),
                               np.asarray(cache.conv), rtol=1e-5, atol=1e-5)


def test_ssm_block_size_invariance():
    cfg = tiny_config("falcon-mamba-7b")
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 37, cfg.d_model))
    y1, f1 = SSM.ssm_forward(cfg, p, x, block=4)
    y2, f2 = SSM.ssm_forward(cfg, p, x, block=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(f1.state), np.asarray(f2.state),
                               rtol=2e-4, atol=2e-4)


def test_mrope_equals_rope_when_positions_agree():
    """With t==h==w position ids, M-RoPE degenerates to plain RoPE."""
    B, S, H, hd = 2, 12, 4, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    pos3 = jnp.broadcast_to(pos[..., None], (B, S, 3))
    got = apply_mrope(x, pos3, 10_000.0, (2, 3, 3))
    want = apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rope_rotation_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 32))
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    y = apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)
