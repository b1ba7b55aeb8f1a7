"""Unit tests for the numeric building blocks: chunked attention vs naive
softmax, MoE dispatch vs dense oracle, SSM scan vs recurrence, M-RoPE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.kernels import compat
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
    want = A.gqa_forward(cfg, p, x, pos)
    counters = {n: A._PATHS.counter(n) for n in ("fused", "chunked")}
    before = {n: c.value for n, c in counters.items()}
    # only the dispatch sees a compiled backend: the kernel itself still
    # resolves to interpret mode here
    monkeypatch.setattr(compat, "compiled_for_tpu", lambda: compiled)
    got = A.gqa_forward(cfg, p, x, pos)
    assert {n: c.value - before[n] for n, c in counters.items()} == {
        n: int(n == path) for n in counters}
    assert got.shape == x.shape
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


def _ssm_stepwise(cfg, p, x):
    """The selective SSM one token at a time: the causal conv over a window
    of the last K inputs, h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t, and
    y_t = C_t h_t + D x_t gated by SiLU(z_t).  No inner norms."""
    B, S, _ = x.shape
    di, N, K, R = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    xin, z = jnp.split(jnp.einsum("bsd,de->bse", x, p["in_proj"]), 2, -1)
    A = -jnp.exp(p["A_log"])
    window = jnp.zeros((B, K - 1, di))
    h = jnp.zeros((B, di, N))
    ys = []
    for t in range(S):
        window = jnp.concatenate([window, xin[:, t:t + 1]], axis=1)
        xc = jax.nn.silu(jnp.einsum("bkd,kd->bd", window, p["conv_w"])
                         + p["conv_b"])
        window = window[:, 1:]
        dt, Bt, Ct = jnp.split(xc @ p["x_proj"], [R, R + N], axis=-1)
        dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
        h = jnp.exp(dt[..., None] * A) * h + (dt * xc)[..., None] \
            * Bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, Ct) + xc * p["D"]
        ys.append(y * jax.nn.silu(z[:, t]))
    return jnp.einsum("bsd,de->bse", jnp.stack(ys, axis=1), p["out_proj"])


def test_ssm_scan_matches_stepwise_decode():
    """Chunked associative scan == token-by-token recurrence."""
    cfg = tiny_config("falcon-mamba-7b")
    assert not cfg.ssm_inner_norms
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    B, S = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.5
    y_scan = SSM.ssm_forward(cfg, p, x, block=8)
    y_step = _ssm_stepwise(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_step),
                               rtol=2e-4, atol=2e-4)


def test_ssm_block_size_invariance():
    cfg = tiny_config("falcon-mamba-7b")
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 37, cfg.d_model))
    y1 = SSM.ssm_forward(cfg, p, x, block=4)
    y2 = SSM.ssm_forward(cfg, p, x, block=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    # the scan's last state, which its gradient carries, as well
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    di, N = cfg.ssm_d_inner, cfg.ssm_state
    args = (jax.random.normal(ks[0], (1, 37, di)),
            jax.nn.softplus(jax.random.normal(ks[1], (1, 37, di))),
            jax.random.normal(ks[2], (1, 37, N)),
            jax.random.normal(ks[3], (1, 37, N)),
            -jnp.exp(p["A_log"]))
    (ya, ha), (yb, hb) = (SSM._chunked_scan(*args, block=b) for b in (4, 64))
    for got, want in ((ya, yb), (ha, hb)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
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


# --------------------------------------------------------------------------
# SSM scan dispatch, Jamba's inner norms and interleaved stack
# --------------------------------------------------------------------------

@pytest.mark.parametrize("compiled,path", [(False, "jnp"), (True, "kernel")])
def test_ssm_forward_dispatch(monkeypatch, compiled, path):
    cfg = tiny_config("falcon-mamba-7b")
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 27, cfg.d_model)) * 0.5
    want = SSM.ssm_forward(cfg, p, x)
    counters = {n: SSM._PATHS.counter(n) for n in ("kernel", "jnp")}
    before = {n: c.value for n, c in counters.items()}
    # only the dispatch sees a compiled backend: the kernel itself still
    # resolves to interpret mode here
    monkeypatch.setattr(compat, "compiled_for_tpu", lambda: compiled)
    got = SSM.ssm_forward(cfg, p, x)
    assert {n: c.value - before[n] for n, c in counters.items()} == {
        n: int(n == path) for n in counters}
    # float32 scans that differ in the order of the decays' products
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _ssm_forward_without_norms(cfg, p, x, block):
    """The selective SSM as ``ssm_forward`` computed it before the inner
    norms and the kernel path: in_proj, causal conv, SiLU, x_proj, dt_proj,
    softplus, the chunked associative scan, D skip, SiLU(z) gate."""
    B, S, _ = x.shape
    di, N, K, R = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    xin, z = jnp.split(jnp.einsum("bsd,de->bse", x, p["in_proj"]), 2, -1)
    xp = jnp.concatenate([jnp.zeros((B, K - 1, di), x.dtype), xin], axis=1)
    conv = sum(xp[:, i:i + S, :] * p["conv_w"][i] for i in range(K))
    xc = jax.nn.silu(conv + p["conv_b"])
    proj = jnp.einsum("bsd,dr->bsr", xc, p["x_proj"])
    dt, Bt, Ct = jnp.split(proj, [R, R + N], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsr,rd->bsd", dt, p["dt_proj"])
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    nb = -(-S // block)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, nb * block - S), (0, 0)))
    blocks = [jnp.moveaxis(pad(a).reshape(B, nb, block, -1), 1, 0)
              for a in (xc, dt, Bt, Ct)]

    def block_step(h, inp):
        xj, dtj, Bj, Cj = inp
        a = jnp.exp(dtj[..., None] * A)
        b = (dtj * xj)[..., None] * Bj[:, :, None, :]
        a_cum, b_cum = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], l[1] * r[0] + r[1]), (a, b), axis=1)
        hs = a_cum * h[:, None] + b_cum
        return hs[:, -1], jnp.einsum("bsdn,bsn->bsd", hs, Cj)

    _, yb = jax.lax.scan(block_step, jnp.zeros((B, di, N)), tuple(blocks))
    y = jnp.moveaxis(yb, 0, 1).reshape(B, nb * block, di)[:, :S]
    y = (y + xc * p["D"]) * jax.nn.silu(z)
    return jnp.einsum("bsd,de->bse", y, p["out_proj"])


def test_ssm_forward_without_inner_norms_is_unchanged():
    """An SSM config without Jamba's norms computes what it computed before
    them, to the bit (float32, the jnp path of the CPU)."""
    cfg = tiny_config("falcon-mamba-7b")
    assert not cfg.ssm_inner_norms
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    assert "dt_norm" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 37, cfg.d_model)) * 0.5
    got = jax.jit(lambda p, x: SSM.ssm_forward(cfg, p, x, block=8))(p, x)
    want = jax.jit(lambda p, x: _ssm_forward_without_norms(
        cfg, p, x, 8))(p, x)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_ssm_inner_norms_scale_dt_b_c():
    """With the inner norms on, dt, B and C go through RMSNorms with their
    own scales: a scale of 2 on B and C quadruples the scan's y term."""
    cfg = tiny_config("jamba2-3b").kind_config("ssm")
    assert cfg.ssm_inner_norms
    p = init_table(jax.random.PRNGKey(0), SSM.ssm_table(cfg))
    assert {p[k].shape for k in ("b_norm", "c_norm")} == {(cfg.ssm_state,)}
    xc = jax.random.normal(jax.random.PRNGKey(1), (1, 5, cfg.ssm_d_inner))
    dt, Bt, Ct = SSM._ssm_coeffs(cfg, p, xc)
    np.testing.assert_allclose(np.sqrt(np.mean(np.square(np.asarray(Bt)),
                                               -1)), 1.0, rtol=1e-3)
    p2 = dict(p, b_norm=2 * p["b_norm"], c_norm=2 * p["c_norm"])
    dt2, Bt2, Ct2 = SSM._ssm_coeffs(cfg, p2, xc)
    assert np.array_equal(np.asarray(dt), np.asarray(dt2))
    np.testing.assert_allclose(np.asarray(Bt2), 2 * np.asarray(Bt),
                               rtol=1e-6)


def test_jamba_layer_kinds_follow_the_period():
    from repro.models import get_config, get_model
    cfg = get_config("jamba2-3b")
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert all(k == "ssm" for i, k in enumerate(kinds) if i % 14 != 7)
    shapes = jax.eval_shape(get_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert shapes["layers"]["ssm"]["ssm"]["in_proj"].shape == (26, 2560,
                                                                10240)
    assert shapes["layers"]["attention"]["attn"]["wk"].shape == (2, 2560,
                                                                 128)
    assert "ssm" not in shapes["layers"]["attention"]
    total, _ = cfg.param_count()
    assert total == sum(int(np.prod(s.shape))
                        for s in jax.tree.leaves(shapes))
    tiny = tiny_config("jamba2-3b")
    assert tiny.layer_kinds == ("ssm", "attention", "ssm", "ssm")


def test_interleaved_stack_runs_its_layers_in_order():
    """The forward equals the layers run one by one in the published order,
    each from its kind's stack; another order gives another answer."""
    from repro.models import get_model, transformer as T
    from repro.models.layers import rms_norm
    cfg = tiny_config("jamba2-3b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))

    def by_hand(kinds):
        h, seen = x, {}
        for kind in kinds:
            i = seen[kind] = seen.get(kind, -1) + 1
            lp = jax.tree.map(lambda a: a[i], params["layers"][kind])
            h = T.block_forward(cfg.kind_config(kind), lp, h, pos)
        h = rms_norm(h, params["embed"]["final_norm"], cfg.norm_eps)
        return jnp.einsum("bsd,vd->bsv", h, params["embed"]["embedding"])

    got = model.forward(params, {"embeds": x})
    # float32, jitted against eager: logits of order 1-10 agree to 1e-4
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(by_hand(cfg.layer_kinds)),
                               rtol=1e-4, atol=1e-4)
    other = by_hand(("attention", "ssm", "ssm", "ssm"))
    assert not np.allclose(np.asarray(got), np.asarray(other), atol=1e-3)


def test_interleaved_stack_trains_and_refuses_decode():
    """The interleaved stack trains: a finite loss, and finite gradients
    that reach the attention layers."""
    from repro.models import get_model
    cfg = tiny_config("jamba2-3b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
        params, {"tokens": tokens, "labels": tokens})
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["layers"]["attention"]["attn"]["wq"]).max()) > 0


def test_rope_none_leaves_queries_and_keys_unrotated():
    cfg = tiny_config("jamba2-3b").kind_config("attention")
    assert cfg.rope_type == "none"
    p = init_table(jax.random.PRNGKey(0), A.gqa_table(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(6), (1, 6))
    q, k, _ = A._project_qkv(cfg, p, x, pos)
    np.testing.assert_array_equal(
        np.asarray(q), np.asarray((x @ p["wq"]).reshape(q.shape)))
    np.testing.assert_array_equal(
        np.asarray(k), np.asarray((x @ p["wk"]).reshape(k.shape)))
    with pytest.raises(ValueError, match="rope_type"):
        cfg.replace(rope_type="alibi")
