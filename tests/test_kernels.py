"""Pallas kernel validation: interpret-mode execution vs ref.py oracles,
swept over shapes and dtypes; hypothesis property tests live in
test_property_based.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.binpipe import BinaryPartition
from repro.kernels import ops, ref


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

ATTN_SHAPES = [
    # B, H, KV, Sq, Sk, hd, causal, window
    (1, 4, 4, 128, 128, 64, True, 0),
    (2, 4, 2, 256, 256, 64, True, 0),       # GQA
    (1, 8, 1, 128, 128, 128, True, 0),      # MQA, hd=128
    (1, 4, 4, 128, 384, 64, True, 0),       # kv longer than q (decode-ish)
    (1, 4, 2, 200, 200, 64, True, 0),       # ragged (padding path)
    (2, 2, 2, 128, 128, 64, False, 0),      # non-causal (cross attention)
    (1, 2, 1, 256, 256, 64, True, 64),      # sliding window
    (1, 25, 5, 128, 128, 64, True, 0),      # hymba's 25q/5kv ratio
]


@pytest.mark.parametrize("B,H,KV,Sq,Sk,hd,causal,window", ATTN_SHAPES)
def test_flash_attention_vs_ref(B, H, KV, Sq, Sk, hd, causal, window):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(Sq + H), 3)
    q = jax.random.normal(kq, (B, H, Sq, hd), jnp.float32)
    k = jax.random.normal(kk, (B, KV, Sk, hd), jnp.float32)
    v = jax.random.normal(kv, (B, KV, Sk, hd), jnp.float32)
    got = ops.attention(q, k, v, causal=causal, window=window,
                        blk_q=64, blk_k=64)
    want = ref.attention_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (2, 4, 128, 64)).astype(dtype)
    k = jax.random.normal(kk, (2, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(kv, (2, 2, 128, 64)).astype(dtype)
    got = ops.attention(q, k, v).astype(jnp.float32)
    want = ref.attention_reference(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    assert ops.attention(q, k, v).dtype == dtype


@pytest.mark.parametrize("blk_q,blk_k", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shape_invariance(blk_q, blk_k):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (1, 2, 160, 64))
    k = jax.random.normal(kk, (1, 2, 160, 64))
    v = jax.random.normal(kv, (1, 2, 160, 64))
    got = ops.attention(q, k, v, blk_q=blk_q, blk_k=blk_k)
    want = ref.attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# inputs in the model's layout, (B, S, H, hd), at head width 128: S 271
# and 64 run the whole sequence as one block, S 1030 sweeps kv in
# 512-blocks
MASKS = {"causal": (True, 0), "noncausal": (False, 0), "window": (True, 200)}


def _bshd(S, G, dtype, seed=0, B=2, KV=2, hd=128):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, S, KV * G, hd)).astype(dtype)
    k = jax.random.normal(kk, (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(kv, (B, S, KV, hd)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("S", [271, 64, 1030])
def test_fused_gqa_attention_matches_chunked_and_ref(S, G, mask):
    from repro.kernels.flash_attention import block_sizes, flash_attention
    from repro.models.attention import chunked_attention
    causal, window = MASKS[mask]
    q, k, v = _bshd(S, G, jnp.bfloat16, seed=S + G)
    whole = block_sizes(S, S, G, 128, 2) == (S, S)
    assert whole == (S < 1024)
    got = flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                          v.swapaxes(1, 2), causal=causal, window=window)
    assert got.shape == q.shape and got.dtype == jnp.bfloat16
    got = np.asarray(got.astype(jnp.float32))
    chunked = chunked_attention(q, k, v, causal=causal, window=window)
    want = ref.attention_reference(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, window=window).swapaxes(1, 2)
    for other in (chunked, want):
        np.testing.assert_allclose(
            got, np.asarray(other.astype(jnp.float32)), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("mask", MASKS)
def test_fused_gqa_attention_grad_is_chunked_grad(mask):
    from repro.models.attention import chunked_attention, fused_attention
    causal, window = MASKS[mask]
    q, k, v = _bshd(96, 4, jnp.float32, seed=7)
    w = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * w)

    fused = loss(lambda q, k, v: fused_attention(q, k, v, causal, window,
                                                 32))
    plain = loss(lambda q, k, v: chunked_attention(
        q, k, v, causal=causal, window=window, chunk=32))
    got = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(fused(q, k, v)), float(plain(q, k, v)),
                               rtol=1e-4)


# --------------------------------------------------------------------------
# selective scan
# --------------------------------------------------------------------------

SCAN_SHAPES = [
    # b, S, di, N, blk_d, blk_s
    (1, 64, 128, 16, 128, 32),
    (2, 128, 256, 16, 128, 64),
    (1, 100, 96, 8, 64, 32),       # ragged both dims
    (2, 37, 128, 16, 128, 128),    # S < blk_s
]


@pytest.mark.parametrize("b,S,di,N,blk_d,blk_s", SCAN_SHAPES)
def test_selective_scan_vs_ref(b, S, di, N, blk_d, blk_s):
    keys = jax.random.split(jax.random.PRNGKey(S + di), 5)
    x = jax.random.normal(keys[0], (b, S, di))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, S, di)) - 1.0)
    B = jax.random.normal(keys[2], (b, S, N))
    C = jax.random.normal(keys[3], (b, S, N))
    A = -jnp.exp(jax.random.normal(keys[4], (di, N)) * 0.5)
    got = ops.mamba_scan(x, dt, B, C, A, blk_d=blk_d, blk_s=blk_s)
    want = ref.selective_scan_reference(x, dt, B, C, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_selective_scan_bf16_inputs():
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(keys[0], (1, 64, 128)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, 64, 128))
                         ).astype(jnp.bfloat16)
    B = jax.random.normal(keys[2], (1, 64, 16)).astype(jnp.bfloat16)
    C = jax.random.normal(keys[3], (1, 64, 16)).astype(jnp.bfloat16)
    A = -jnp.exp(jax.random.normal(keys[4], (128, 16)) * 0.5)
    got = ops.mamba_scan(x, dt, B, C, A)
    want = ref.selective_scan_reference(x, dt, B, C, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


def test_selective_scan_matches_model_ssm():
    """The kernel and the model's associative-scan path agree."""
    from repro.configs import tiny_config
    from repro.models import ssm as SSM
    from repro.models.layers import init_table
    cfg = tiny_config("falcon-mamba-7b")
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    b, S = 2, 48
    x = jax.random.normal(jax.random.PRNGKey(1), (b, S, cfg.d_model)) * 0.5
    # reproduce the model's pre-scan pipeline
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)
    xc = jax.nn.silu(SSM._causal_conv(cfg, p, xin))
    dt, Bt, Ct = SSM._ssm_coeffs(cfg, p, xc)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    got = ops.mamba_scan(xc.astype(jnp.float32), dt, Bt, Ct, A,
                         blk_d=64, blk_s=16)
    want = ref.selective_scan_reference(xc, dt, Bt, Ct, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _scan_inputs(b, S, di, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (b, S, di)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, S, di)) - 1.0),
            jax.random.normal(ks[2], (b, S, N)),
            jax.random.normal(ks[3], (b, S, N)),
            -jnp.exp(jax.random.normal(ks[4], (di, N)) * 0.5))


@pytest.mark.parametrize("b,S,di,N,blk_s", [
    (2, 271, 256, 16, None),    # the cell's odd S: one whole-sequence block
    (1, 271, 384, 16, 128),     # S cut into 128-step blocks, last one short
    (2, 37, 128, 8, None),
])
def test_selective_scan_kernel_matches_jnp_scan_and_ref(b, S, di, N, blk_s):
    """The kernel's y and last state against the model's jnp chunked scan
    and the sequential oracle.  All three run in float32; they differ only
    in the order of the decays' products (the associative scan pairs them
    in a tree), so 2e-4 on outputs of order 1-10 is rounding."""
    from repro.kernels.selective_scan import selective_scan
    from repro.models.ssm import _chunked_scan
    x, dt, B, C, A = _scan_inputs(b, S, di, N)
    y, h = selective_scan(x, dt, B, C, A, blk_s=blk_s)
    y_jnp, h_jnp = _chunked_scan(x, dt, B, C, A, block=64)
    want = ref.selective_scan_reference(x, dt, B, C, A)
    assert y.shape == (b, S, di) and h.shape == (b, di, N)
    for got, w in ((y, want), (y, y_jnp), (h, h_jnp)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_selective_scan_kernel_grad_is_jnp_grad():
    """``kernel_scan``'s VJP is the jnp scan's, recomputed from the inputs;
    with a loss linear in y and the state the cotangents are the same, so
    the gradients are the same arithmetic."""
    from repro.models.ssm import _chunked_scan, kernel_scan
    args = _scan_inputs(2, 40, 128, 8, seed=3)
    wy = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 128))
    wh = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 8))

    def loss(scan):
        def f(*a):
            y, h = scan(*a)
            return jnp.sum(y * wy) + jnp.sum(h * wh)
        return f

    got = jax.grad(loss(lambda *a: kernel_scan(*a, 16)),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(lambda *a: _chunked_scan(*a, block=16)),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# sensor decode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("R,Nb,blk_r,blk_n", [
    (8, 512, 8, 256), (5, 300, 8, 128), (33, 1024, 16, 512), (1, 128, 8, 512),
])
def test_sensor_decode_vs_ref(R, Nb, blk_r, blk_n):
    rng = np.random.RandomState(R + Nb)
    payload = jnp.asarray(rng.randint(0, 256, (R, Nb), np.uint8))
    scale = jnp.asarray(rng.rand(R).astype(np.float32) * 0.1)
    zp = jnp.asarray(rng.randint(0, 255, R).astype(np.float32))
    lengths = jnp.asarray(rng.randint(0, Nb + 1, R).astype(np.int32))
    got = ops.decode_records(payload, scale, zp, lengths,
                             blk_r=blk_r, blk_n=blk_n)
    want = ref.sensor_decode_reference(payload, scale, zp, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("R,Nb,blk_r,blk_n", [
    (8, 512, 8, 256), (5, 300, 8, 128), (33, 1024, 16, 512), (1, 128, 8, 512),
])
def test_sensor_decode_metrics_fuses_decode_and_reductions(R, Nb, blk_r,
                                                           blk_n):
    """The fused kernel's features equal sensor_decode's; its per-record
    reductions (digest / count / min / max) match a numpy oracle over the
    valid prefix of each record."""
    from repro.kernels.sensor_decode import sensor_decode_metrics
    rng = np.random.RandomState(R + Nb)
    payload = rng.randint(0, 256, (R, Nb)).astype(np.uint8)
    scale = rng.rand(R).astype(np.float32) * 0.1
    zp = rng.randint(0, 255, R).astype(np.float32)
    lengths = rng.randint(0, Nb + 1, R).astype(np.int32)
    lengths[0] = 0                       # empty-record sentinel path
    ts_low = rng.randint(0, 2**32, R, dtype=np.uint64).astype(np.uint32)
    out = sensor_decode_metrics(
        jnp.asarray(payload), jnp.asarray(scale), jnp.asarray(zp),
        jnp.asarray(lengths), jnp.asarray(ts_low),
        blk_r=blk_r, blk_n=blk_n)
    want = ref.sensor_decode_reference(payload, scale, zp, lengths)
    np.testing.assert_allclose(np.asarray(out["features"]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(out["counts"]), lengths)
    mn, mx = np.asarray(out["min_byte"]), np.asarray(out["max_byte"])
    for r in range(R):
        valid = payload[r, :lengths[r]]
        assert mn[r] == (valid.min() if lengths[r] else 255)
        assert mx[r] == (valid.max() if lengths[r] else 0)


def test_sensor_decode_metrics_digest_bit_identical_to_jitted():
    """Acceptance (ISSUE 3): the fused kernel's record digests reduce to
    exactly the aggregation layer's jitted checksum — bit-identical, for
    every block shape — so golden verdicts survive the fused upgrade."""
    from repro.core.aggregation import _jitted, combine_digests
    from repro.kernels.sensor_decode import sensor_decode_metrics
    rng = np.random.RandomState(3)
    R, Nb = 21, 640
    payload = rng.randint(0, 256, (R, Nb)).astype(np.uint8)
    lengths = rng.randint(0, Nb + 1, R).astype(np.int32)
    ts_low = rng.randint(0, 2**32, R, dtype=np.uint64).astype(np.uint32)
    scale = np.ones(R, np.float32)
    zp = np.zeros(R, np.float32)
    want_records = np.asarray(_jitted()["record_digest"](
        jnp.asarray(payload), jnp.asarray(lengths), jnp.asarray(ts_low)))
    want_total = int(_jitted()["digest"](
        jnp.asarray(payload), jnp.asarray(lengths), jnp.asarray(ts_low)))
    for blk_r, blk_n in [(8, 512), (4, 128), (21, 640), (16, 256)]:
        out = sensor_decode_metrics(
            jnp.asarray(payload), jnp.asarray(scale), jnp.asarray(zp),
            jnp.asarray(lengths), jnp.asarray(ts_low),
            blk_r=blk_r, blk_n=blk_n)
        got = np.asarray(out["record_digests"])
        assert got.dtype == np.uint32
        assert np.array_equal(got, want_records)
        assert combine_digests(got) == want_total


def test_decode_partition_end_to_end():
    """core.binpipe partition -> on-device feature matrix (the full Fig 4
    path: encode -> serialize -> frame -> device decode)."""
    recs = [bytes(range(i, i + 50)) for i in range(0, 200, 50)]
    part = BinaryPartition(list(recs))
    feats = ops.decode_partition(part, feature_bytes=64)
    assert feats.shape == (4, 64)
    # first record: bytes 0..49 scaled by 1/255, then zero padding
    np.testing.assert_allclose(np.asarray(feats[0, :50]),
                               np.arange(50, dtype=np.float32) / 255.0,
                               rtol=1e-6)
    assert float(jnp.abs(feats[0, 50:]).max()) == 0.0
