"""Per-architecture smoke tests: reduced same-family config, one forward +
one train-grad step + causality of the forward on CPU.  Asserts output
shapes and no NaNs.  Full configs are traced at their published widths
from shapes alone (``jax.eval_shape``, no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, tiny_config
from repro.models import get_config, get_model

B, S = 2, 32

#: every registered config; the decoder-only ones
CONFIGS = ALL_ARCHS + ["jamba2-3b"]
DECODERS = [a for a in CONFIGS if a != "seamless-m4t-large-v2"]


def _batch(cfg, key):
    kt, kl, ke = jax.random.split(key, 3)
    if cfg.is_encoder_decoder:
        return {
            "frames": jax.random.normal(ke, (B, S, cfg.d_model),
                                        jnp.float32),
            "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
            "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
        }
    if cfg.frontend == "vision":
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :, None],
                               (B, S, 3))
        return {
            "embeds": jax.random.normal(ke, (B, S, cfg.d_model), jnp.float32),
            "positions": pos,
            "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
        }
    return {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = tiny_config(arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits = jax.jit(model.forward)(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_grad_step(arch):
    cfg = tiny_config(arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(params, batch)
    assert bool(jnp.isfinite(loss)), f"{arch}: loss={loss}"
    # a sensible init loses ~ln(V) on random labels
    assert float(loss) < 3 * np.log(cfg.vocab_size)
    finite = jax.tree.map(lambda g: bool(jnp.isfinite(g).all()), grads)
    assert all(jax.tree.leaves(finite)), f"{arch}: non-finite grads"
    norms = [float(jnp.abs(g).max()) for g in jax.tree.leaves(grads)]
    assert max(norms) > 0, f"{arch}: all-zero grads"


@pytest.mark.parametrize("arch", DECODERS)
def test_forward_is_causal(arch):
    """Changing the inputs after position t leaves the logits up to t
    bit-equal."""
    cfg = tiny_config(arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    other = _batch(cfg, jax.random.PRNGKey(2))
    t = S // 2
    key = "embeds" if "embeds" in batch else "tokens"
    changed = dict(batch)
    changed[key] = batch[key].at[:, t + 1:].set(other[key][:, t + 1:])
    assert not np.array_equal(np.asarray(changed[key]),
                              np.asarray(batch[key]))
    fwd = jax.jit(model.forward)
    want = np.asarray(fwd(params, batch))
    got = np.asarray(fwd(params, changed))
    assert np.array_equal(got[:, :t + 1], want[:, :t + 1])
    assert not np.array_equal(got[:, t + 1:], want[:, t + 1:])


@pytest.mark.parametrize("arch", CONFIGS)
def test_published_forward_shape(arch):
    """The forward at published widths, traced from shapes alone: logits
    (1, 8, V) in the config's dtype."""
    cfg = get_config(arch)
    model = get_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    if cfg.is_encoder_decoder:
        batch = {"frames": jax.ShapeDtypeStruct((1, 8, cfg.d_model),
                                                jnp.float32),
                 "tokens": tokens}
    elif cfg.frontend == "vision":
        batch = {"embeds": jax.ShapeDtypeStruct((1, 8, cfg.d_model),
                                                jnp.float32),
                 "positions": jax.ShapeDtypeStruct((1, 8, 3), jnp.int32)}
    else:
        batch = {"tokens": tokens}
    logits = jax.eval_shape(model.forward, params, batch)
    assert logits.shape == (1, 8, cfg.padded_vocab)
    assert logits.dtype == jnp.dtype(cfg.dtype)


def test_param_counts_match_nominal():
    """The full configs really are the published model sizes."""
    import repro.models as M
    nominal = {
        "hymba-1.5b": 1.5e9, "granite-moe-1b-a400m": 1.3e9,
        "grok-1-314b": 314e9, "yi-34b": 34e9, "minicpm3-4b": 4e9,
        "qwen3-4b": 4e9, "qwen2.5-32b": 32e9, "qwen2-vl-7b": 7e9,
        "seamless-m4t-large-v2": 2.3e9, "falcon-mamba-7b": 7e9,
    }
    for arch, n in nominal.items():
        tot, act = M.get_config(arch).param_count()
        assert 0.7 * n < tot < 1.35 * n, (arch, tot, n)
        assert act <= tot
