"""The jamba2-3b configuration's benchmark pieces at test widths on the CPU:
the plain reference against the program, the FLOP and scan-byte counters
against hand counts, the ``ssm_share`` and ``scan_roofline`` readers, and
whole runs of a tiny Jamba cell."""

import gzip
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import BENCH, DATA

import attribution
import devtrace
import run
from harness import load_json, load_module, metric_reader, register_config
from harness import resolve_cell
from reference.common import decode_features

SEED = 1234
REF = load_module("reference", "jamba")
FLOPS = load_module("flops", "jamba")


def small(dtype):
    c = load_json(os.path.join(DATA, "configs", "jamba-small.json"))
    c["name"] = f"jamba-small-{dtype}"
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


def published():
    return load_json(os.path.join(BENCH, "configs", "jamba2-3b.json"))


# -- the reference against the program ----------------------------------------

def test_weights_are_drawn_alike():
    """Layer i of either kind draws from the i-th layer key; the program
    stacks each kind's layers in order."""
    c = small("bfloat16")
    cfg, params = program(c)
    d = REF.dims(c)
    keys = jax.random.split(jax.random.split(jax.random.PRNGKey(SEED))[1],
                            d.L)
    seen = {}
    for i in range(d.L):
        attn = REF.is_attention(d, i)
        kind = "attention" if attn else "ssm"
        j = seen[kind] = seen.get(kind, -1) + 1
        want = REF.layer_weights(d, keys[i], attn)
        got = jax.tree.map(lambda a: a[j], params["layers"][kind])
        table = got["mlp"] | (got["attn"] if attn else got["ssm"])
        assert set(table) <= set(want), (i, set(table) - set(want))
        for k, v in table.items():
            assert np.array_equal(np.asarray(v, np.float32),
                                  np.asarray(want[k])), (i, k)
    assert seen == {"ssm": 2, "attention": 0}


def test_float32_program_matches_reference():
    # float32 throughout, the same weights: what is left is summation
    # order, of order 1e-6 of the largest logit
    c = small("float32")
    cfg, params = program(c)
    feats = features(3, 10, 64)
    want = REF.forward(c, SEED, feats.reshape(3, 10, 64))
    assert gap(program_logits(cfg, params, feats), want) < 1e-5


def test_program_tiny_config_matches_reference():
    """The program's own ``jamba2-3b-tiny`` (4 layers, period 4, offset 1),
    restated in the published config's keys."""
    from repro.configs import tiny_config
    from repro.perception import init_params
    cfg = tiny_config("jamba2-3b")
    params = init_params(cfg, SEED)
    c = {"family": "jamba", "hidden_size": cfg.d_model,
         "num_hidden_layers": cfg.num_layers,
         "attn_layer_period": cfg.attn_layer_period,
         "attn_layer_offset": cfg.attn_layer_offset,
         "num_attention_heads": cfg.num_heads,
         "num_key_value_heads": cfg.num_kv_heads,
         "intermediate_size": cfg.d_ff,
         "mamba_expand": cfg.ssm_d_inner // cfg.d_model,
         "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
         "mamba_dt_rank": cfg.ssm_dt_rank, "rms_norm_eps": cfg.norm_eps,
         "tie_word_embeddings": cfg.tie_embeddings, "out_features": 16,
         "weights": {"dtype": cfg.dtype,
                     "embedding_rows": cfg.padded_vocab}}
    feats = features(2, 6, cfg.d_model)
    want = REF.forward(c, SEED, feats.reshape(2, 6, cfg.d_model))
    assert gap(program_logits(cfg, params, feats), want) < 1e-5


def test_control_is_further_off_than_bfloat16():
    # the program in bfloat16 against the reference in float32 is bfloat16
    # rounding, compounded over 4 layers; the fp8 control rounds every
    # linear layer's operands to 3 mantissa bits
    c = small("bfloat16")
    cfg, params = program(c)
    feats = features(3, 10, 64)
    want = REF.forward(c, SEED, feats.reshape(3, 10, 64))
    served = gap(program_logits(cfg, params, feats), want)
    fp8 = gap(REF.forward(c, SEED, feats.reshape(3, 10, 64), "fp8"), want)
    assert 0 < served < 0.1
    assert fp8 > 3 * served


def test_layer_order_is_the_published_convention():
    d = REF.dims(published())
    assert [i for i in range(d.L) if REF.is_attention(d, i)] == [7, 21]
    assert (d.H, d.KV, d.hd, d.di, d.N, d.K, d.R) == (20, 1, 128, 5120, 16,
                                                      4, 160)


# -- counters -----------------------------------------------------------------

def test_layer_params_by_hand():
    # attention: q 2560x2560, k and v 2560x128, o 2560x2560; Mamba:
    # in_proj 2560x10240, conv 4x5120 + bias, x_proj 5120x192, the three
    # inner norms 160 + 16 + 16, dt_proj 160x5120 + bias, A_log 5120x16,
    # D, out_proj 5120x2560; each with an MLP of 3 x 2560 x 8192 and two
    # norms
    common = 62_914_560 + 5_120
    attn = 6_553_600 + 2 * 327_680 + 6_553_600 + common
    mamba = (26_214_400 + 20_480 + 5_120 + 983_040 + 192 + 819_200 + 5_120
             + 81_920 + 5_120 + 13_107_200 + common)
    c = published()
    assert FLOPS.layer_params(c) == (attn, mamba) == (76_682_240,
                                                      104_161_472)
    assert FLOPS.non_embedding_params(c) == 2 * attn + 26 * mamba == \
        2_861_562_752


def test_counts_agree_with_the_program_config():
    from repro.models import get_config
    c = published()
    cfg = get_config(register_config(c)[len("perception://"):])
    total, _ = cfg.param_count()
    emb = c["vocab_size"] * c["hidden_size"]
    assert total - emb - c["hidden_size"] == FLOPS.non_embedding_params(c)


def test_step_flops_by_hand():
    # jamba-small: D 64, 4 heads of 16 on 1 kv head, MLP 96, d_inner 128,
    # N 8, conv 4, dt rank 8; layers ssm, attention, ssm, ssm
    c = small("bfloat16")
    mlp = 2 * 3 * 64 * 96                                      # 36,864
    attn_tok = 2 * (64 * 64 + 2 * 64 * 16 + 64 * 64) + mlp     # 57,344
    mamba_tok = (2 * (64 * 256 + 128 * 24 + 8 * 128 + 128 * 64)
                 + 2 * 4 * 128 + 6 * 128 * 8 + 128 + mlp)     # 101,504
    assert (attn_tok, mamba_tok) == (57_344, 101_504)
    causal = 4 * 4 * 16 * 10 * 11 // 2                        # 14,080
    per_row = 10 * (attn_tok + 3 * mamba_tok) + causal + 2 * 64 * 16
    assert FLOPS.step_flops(c, 3, 10) == 3 * per_row == 10_904_064
    # the published config: about 5.73 GFLOP a token
    assert FLOPS.step_flops(published(), 1, 1) == pytest.approx(5.7308e9,
                                                                rel=1e-4)


def test_scan_bytes_by_hand():
    # per Mamba layer: x, dt, y (rows, tokens, d_inner) and B, C (rows,
    # tokens, N) at 4 bytes, and A (d_inner, N)
    c = small("bfloat16")
    per_layer = 4 * (3 * 3 * 10 * 128 + 2 * 3 * 10 * 8 + 128 * 8)
    assert FLOPS.scan_bytes(3, 10, c) == 3 * per_layer == 156_288
    # the cell: 16 rows of 271 tokens, 267,286,528 bytes a layer-call
    assert FLOPS.scan_bytes(16, 271, published()) == 26 * 267_286_528


# -- the readers --------------------------------------------------------------

HLO = """\
HloModule jit_step

%fused_computation.1 (p0: bf16[4,8]) -> bf16[4,8] {
  %p0 = bf16[4,8]{1,0} parameter(0)
  ROOT %dot.1 = bf16[4,8]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/while/body/ssm/bsd,de->bse/dot_general"}
}

%fused_computation.2 (p0: bf16[4,8]) -> bf16[4,8] {
  %p0 = bf16[4,8]{1,0} parameter(0)
  ROOT %dot.2 = bf16[4,8]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/while/body/mlp/bsd,df->bsf/dot_general"}
}

ENTRY %main (x: bf16[4,8]) -> bf16[4,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %fusion.1 = bf16[4,8]{1,0} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/ssm/add"}
  %_selective_scan.3 = (f32[4,8]{1,0}, f32[4,8]{1,0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/ssm/jit(_selective_scan)/ssm_scan/pallas_call"}
  ROOT %fusion.2 = bf16[4,8]{1,0} fusion(%x), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step)/while/body/mlp/add"}
}
"""


def jamba_readings(trace, step_calls, config):
    return devtrace.Readings(
        config=config, flops=FLOPS, peaks={"hbm_bytes_per_s": 819e9},
        drive_s=1.0, scenarios=1, spans=[], step_calls=list(step_calls),
        trace=trace, lo=0, hi=2_000_000, compiles=0)


def test_ssm_readers_on_a_step_with_the_scan(monkeypatch):
    # one step at 0-1 ms: the ssm projection fusion 0.2 ms, the scan
    # kernel 0.5 ms, the MLP 0.3 ms
    prog = attribution.hlo_program(HLO)
    assert attribution.in_scope(prog.scopes["_selective_scan.3"],
                                "ssm_scan")
    assert attribution.in_scope(prog.scopes["_selective_scan.3"], "ssm")
    monkeypatch.setattr(attribution, "step_program", lambda r: prog)
    tr = devtrace.Trace(
        ops={"d": [("fusion.1", 0, 200_000),
                   ("_selective_scan.3", 200_000, 500_000),
                   ("fusion.2", 700_000, 300_000)]},
        modules={"d": [("jit_step(1)", 0, 1_000_000)]},
        host=[("bench.suite", 0, 2_000_000)])
    c = small("bfloat16")
    r = jamba_readings(tr, [(3, 640)], c)
    assert metric_reader("ssm_share").read(r) == pytest.approx(70.0)
    want = 100.0 * FLOPS.scan_bytes(3, 10, c) / 819e9 / 500e-6
    assert metric_reader("scan_roofline").read(r) == pytest.approx(want)


def test_ssm_readers_read_nothing_without_a_scan(monkeypatch):
    """On a recorded qwen3-4b suite (no ``ssm`` scope, no ``scan_bytes``)
    both readers give None and raise nothing."""
    with gzip.open(os.path.join(
            DATA, "trace-qwen3-4b.lidar-scoped.json.gz"), "rt") as f:
        d = json.load(f)
    tr = devtrace.from_json(d)
    lo, hi = devtrace.marker(tr, "bench.suite")
    prog = attribution.StepProgram(d["scopes"], frozenset(d["fusions"]))
    r = devtrace.Readings(
        config={"name": "qwen3-4b", "out_features": 16,
                "model": {"d_model": 2560}},
        flops=load_module("flops", "qwen3"), peaks={"hbm_bytes_per_s": 819e9},
        drive_s=d["drive_s"], scenarios=4, spans=[],
        step_calls=[tuple(s) for s in d["step_calls"]], trace=tr, lo=lo,
        hi=hi, compiles=0)
    monkeypatch.setattr(attribution, "step_program", lambda r: prog)
    for name in ("ssm_share", "scan_roofline"):
        assert metric_reader(name).read(r) is None, name


# -- whole runs of a tiny cell ------------------------------------------------

def tiny_cell():
    return resolve_cell(load_json(f"{DATA}/BENCHMARK-jamba.json"),
                        "jamba-small.tiny", DATA)


def test_sound_jamba_run_is_correct():
    res = run.run_cell(tiny_cell(), 2**33 + 5, 0.0, False, {},
                       time.perf_counter())
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 2


def test_control_in_the_jamba_programs_place_is_caught(monkeypatch):
    """The reference at fp8, put where the program computes the logits."""
    from repro.perception import PerceptionStep
    cell = tiny_cell()

    def control(self, batch):
        feats = np.stack([decode_features(p[:n], 64) for p, n in
                          zip(batch["payload"], batch["lengths"])])
        return jnp.asarray(cell.reference.forward(
            cell.config, self.seed, feats, "fp8")), None
    monkeypatch.setattr(PerceptionStep, "step_arrays", control)
    res = run.run_cell(cell, 2**33 + 5, 0.0, False, {}, time.perf_counter())
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
