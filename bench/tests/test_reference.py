"""The plain references against the program's forward on seeded weights,
at test widths on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DATA

from harness import load_module, register_config

SEED = 1234


def small(name, dtype):
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        c = json.load(f)
    c["name"] = f"{name}-{dtype}"
    c["model"]["dtype"] = c["weights"]["dtype"] = dtype
    return c


def program(c):
    from repro.models import get_config
    from repro.perception import init_params
    cfg = get_config(register_config(c)[len("perception://"):])
    return cfg, init_params(cfg, SEED)


def features(rows, tokens, d):
    rng = np.random.default_rng(7)
    return rng.integers(0, 256, (rows, tokens * d)).astype(np.float32) / 255


def gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def program_logits(cfg, params, feats):
    from repro.perception import features_to_logits
    return np.asarray(jax.jit(
        lambda p, f: features_to_logits(cfg, p, f, 16))(
            params, jnp.asarray(feats)))


@pytest.mark.parametrize("name", ["qwen3-small", "mamba-small"])
def test_weights_are_drawn_alike(name):
    c = small(name, "bfloat16")
    cfg, params = program(c)
    ref = load_module("reference", c["family"])
    d = ref.dims(c)
    keys = jax.random.split(jax.random.split(jax.random.PRNGKey(SEED))[1],
                            d.L)
    for i in range(d.L):
        want = ref.layer_weights(d, keys[i])
        got = jax.tree.map(lambda a: a[i], params["layers"])
        table = got["attn"] | got["mlp"] if "attn" in got else got["ssm"]
        for k, v in table.items():
            if k in want:
                assert np.array_equal(np.asarray(v, np.float32),
                                      np.asarray(want[k])), (i, k)


@pytest.mark.parametrize("name", ["qwen3-small", "mamba-small"])
def test_float32_program_matches_reference(name):
    c = small(name, "float32")
    cfg, params = program(c)
    feats = features(3, 10, 64)
    want = load_module("reference", c["family"]).forward(
        c, SEED, feats.reshape(3, 10, 64))
    assert gap(program_logits(cfg, params, feats), want) < 1e-5


@pytest.mark.parametrize("arch", ["qwen3-4b", "falcon-mamba-7b"])
def test_program_tiny_configs_match_reference(arch):
    """The program's own ``<arch>-tiny`` configs (untied heads), restated
    in the reference's keys."""
    from repro.configs import tiny_config
    from repro.perception import features_to_logits, init_params
    cfg = tiny_config(arch)
    params = init_params(cfg, SEED)
    if cfg.has_attention:
        c = {"family": "qwen3", "hidden_size": cfg.d_model,
             "num_hidden_layers": cfg.num_layers,
             "num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads,
             "head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
             "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
             "tie_word_embeddings": cfg.tie_embeddings}
    else:
        c = {"family": "mamba", "d_model": cfg.d_model,
             "n_layer": cfg.num_layers, "d_inner": cfg.ssm_d_inner,
             "d_state": cfg.ssm_state, "d_conv": cfg.ssm_conv,
             "dt_rank": cfg.ssm_dt_rank, "norm_eps": cfg.norm_eps,
             "tie_embeddings": cfg.tie_embeddings}
    c.update(out_features=16, weights={"dtype": cfg.dtype,
                                       "embedding_rows": cfg.padded_vocab})
    feats = features(2, 6, cfg.d_model)
    got = np.asarray(jax.jit(lambda p, f: features_to_logits(
        cfg, p, f, 16))(params, jnp.asarray(feats)))
    want = load_module("reference", c["family"]).forward(
        c, SEED, feats.reshape(2, 6, cfg.d_model))
    assert gap(got, want) < 1e-5


@pytest.mark.parametrize("name", ["qwen3-small", "mamba-small"])
def test_controls_are_further_off_than_bfloat16(name):
    c = small(name, "bfloat16")
    cfg, params = program(c)
    feats = features(3, 10, 64)
    ref = load_module("reference", c["family"])
    want = ref.forward(c, SEED, feats.reshape(3, 10, 64))
    served = gap(program_logits(cfg, params, feats), want)
    fp8 = gap(ref.forward(c, SEED, feats.reshape(3, 10, 64), "fp8"), want)
    assert 0 < served < 0.02
    assert fp8 > 3 * served


def test_decode_features_cut_to_tokens():
    from reference.common import decode_features
    x = decode_features(np.full(200_000, 255, np.uint8), 2560)
    assert x.shape == (78, 2560) and float(x.min()) == 1.0
    x = decode_features(np.arange(300, dtype=np.uint8), 64)
    assert x.shape == (6, 64) and x[4, 44] == 0.0 and x[4, 43] > 0
