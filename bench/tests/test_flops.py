"""The FLOP and byte counters against hand-worked counts."""

import json
import os

import pytest
from conftest import BENCH

from harness import load_module


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen3_4b_non_embedding_params():
    # 36 x (attention 26,214,400 + MLP 74,711,040 + norms 5,376)
    assert load_module("flops", "qwen3").non_embedding_params(
        config("qwen3-4b")) == 3_633_509_376


def test_mamba_2_8b_layer_shapes():
    # in_proj 2560x10240, conv 4x5120 + bias, x_proj 5120x192,
    # dt_proj 160x5120 + bias, A_log 5120x16, D, out_proj 5120x2560, norm
    per_layer = (26_214_400 + 20_480 + 5_120 + 983_040 + 819_200 + 5_120
                 + 81_920 + 5_120 + 13_107_200 + 2_560)
    m = load_module("flops", "mamba")
    assert m.layer_params(config("mamba-2.8b")) == per_layer == 41_244_160
    assert m.non_embedding_params(config("mamba-2.8b")) == 64 * per_layer


def test_qwen3_step_flops_by_hand():
    c = config("qwen3-4b")
    f = load_module("flops", "qwen3").step_flops
    linear = 2 * (26_214_400 + 74_711_040)     # per token per layer
    head = 2 * 2560 * 16
    # one token: attention sees one position (4 * 32 heads * 128)
    assert f(c, 1, 1) == 36 * (linear + 4 * 32 * 128) + head
    # 271 tokens: causal attention over 271 * 272 / 2 (query, key) pairs
    assert f(c, 10, 271) == 10 * (
        36 * (271 * linear + 4 * 32 * 128 * 271 * 272 // 2) + head)


def test_mamba_step_flops_by_hand():
    c = config("mamba-2.8b")
    per_token = (2 * (26_214_400 + 983_040 + 819_200 + 13_107_200)
                 + 2 * 4 * 5120 + 6 * 5120 * 16 + 5120)
    assert load_module("flops", "mamba").step_flops(c, 6, 78) == \
        6 * (64 * 78 * per_token + 2 * 2560 * 16)


def test_decode_bytes():
    dec = load_module("flops", "sensor_decode").decode_bytes
    assert dec(10, 694_400) == 10 * 694_400 * 5 + 120


@pytest.mark.parametrize("name", ["qwen3-4b"])
def test_counts_agree_with_the_program_config(name):
    # the program's own parameter count of the registered benchmark config
    from harness import register_config
    from repro.models import get_config
    c = config(name)
    cfg = get_config(register_config(c)[len("perception://"):])
    total, _ = cfg.param_count()
    emb = c["vocab_size"] * c["hidden_size"]
    assert total - emb - c["hidden_size"] == load_module(
        "flops", "qwen3").non_embedding_params(c)
