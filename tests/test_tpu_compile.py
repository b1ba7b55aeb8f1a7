"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the installed TPU compiler lowers and compiles the Pallas
decode and attention kernels and the full-width perception step for a chip that is
described, not attached, so what Mosaic or XLA would refuse on the chip
(casts, reductions, tiling, memory) fails here first.  The topology is
described inside a fixture, never at import time: only one process may
load the TPU library, and every test worker imports this module.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

#: v5e HBM per chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9

#: chip_smoke.py's shapes: the perception step's 32-record batches of
#: 81,920-byte records (one topic's half of a 64-record replay window),
#: and the metrics sink's 256-row batches of 128-byte output records and
#: of 81,920-byte input records
STEP_ROWS, RECORD_BYTES = 32, 81920
DECODE_SHAPES = [(STEP_ROWS, RECORD_BYTES), (256, 128), (256, RECORD_BYTES)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _batch_specs(one_chip, R, Nb, metrics):
    specs = [_spec(one_chip, (R, Nb), jnp.uint8),
             _spec(one_chip, (R,), jnp.float32),
             _spec(one_chip, (R,), jnp.float32),
             _spec(one_chip, (R,), jnp.int32)]
    if metrics:
        specs.append(_spec(one_chip, (R,), jnp.uint32))
    return specs


@pytest.mark.parametrize("metrics", [False, True],
                         ids=["sensor_decode", "sensor_decode_metrics"])
@pytest.mark.parametrize("R,Nb", DECODE_SHAPES)
def test_decode_kernels_compile_for_v5e(one_chip, R, Nb, metrics):
    from repro.kernels.sensor_decode import (sensor_decode,
                                            sensor_decode_metrics)
    kernel = sensor_decode_metrics if metrics else sensor_decode
    lowered = jax.jit(functools.partial(kernel, interpret=False)).lower(
        *_batch_specs(one_chip, R, Nb, metrics))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_full_width_param_init_fits_one_v5e(one_chip):
    from repro.models import get_model
    from repro.perception import resolve_config

    # the program repro.perception.init_params jits, for the described chip
    cfg = resolve_config("qwen3-4b")
    init = get_model(cfg).init_params
    key = _spec(one_chip, (2,), jnp.uint32)
    compiled = jax.jit(init).lower(key).compile()
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves(jax.eval_shape(init, key))
    n_params = sum(int(np.prod(s.shape)) for s in leaves)
    # every parameter is drawn in bf16, with no f32 copy of the model, or
    # even of one layer's share of it, held on the way
    assert {s.dtype for s in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert 2 * n_params <= mem.output_size_in_bytes < 2.01 * n_params  # tiles
    assert mem.temp_size_in_bytes < 4 * n_params / cfg.num_layers
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


#: the fused attention kernel's shapes: the qwen3-4b cell's (16 rows of
#: 271 tokens, 32 query heads on 8 kv heads of 128), whole-sequence
#: blocks; longer sequences that sweep kv, with and without a window; and
#: the jamba2-3b cell's MQA (20 query heads on 1 kv head)
ATTN_SHAPES = [(16, 271, 32, 8, True, 0), (16, 271, 32, 8, False, 0),
               (2, 1030, 32, 8, True, 0), (1, 4096, 40, 8, True, 1024),
               (16, 271, 20, 1, True, 0)]


@pytest.mark.parametrize("B,S,H,KV,causal,window", ATTN_SHAPES)
def test_fused_attention_compiles_for_v5e(one_chip, B, S, H, KV, causal,
                                          window):
    from repro.kernels.flash_attention import flash_attention
    q = _spec(one_chip, (B, H, S, 128), jnp.bfloat16)
    kv = _spec(one_chip, (B, KV, S, 128), jnp.bfloat16)
    lowered = jax.jit(functools.partial(
        flash_attention, causal=causal, window=window,
        interpret=False)).lower(q, kv, kv)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_full_width_perception_step_fits_one_v5e(one_chip, monkeypatch):
    from repro.kernels.compat import INTERPRET_ENV
    from repro.models import get_model
    from repro.perception import build_step, resolve_config

    # kernels compiled, as on the chip: attention takes the fused kernel
    monkeypatch.setenv(INTERPRET_ENV, "0")
    cfg = resolve_config("qwen3-4b")
    shapes = jax.eval_shape(get_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), shapes)
    step = build_step(cfg, out_features=16, metrics=False, donate=False,
                      interpret=False)
    compiled = step.lower(params, *_batch_specs(
        one_chip, STEP_ROWS, RECORD_BYTES, False)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%_flash_attention" in text
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used
    # the bf16 params are most of it: 4.0 B of them at 2 bytes each
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 4.0e9 < n_params < 4.5e9


#: the selective scan's shapes: the jamba2-3b cell's (16 rows of 271
#: steps, d_inner 5120, N 16), the whole sequence one block; and a long
#: sequence cut into blocks, the last one short
SCAN_SHAPES = [(16, 271, 5120, 16), (2, 4100, 5120, 16)]


@pytest.mark.parametrize("b,S,di,N", SCAN_SHAPES)
def test_selective_scan_compiles_for_v5e(one_chip, b, S, di, N):
    from repro.kernels.selective_scan import selective_scan
    f32 = jnp.float32
    lowered = jax.jit(functools.partial(selective_scan, interpret=False)).lower(
        _spec(one_chip, (b, S, di), f32), _spec(one_chip, (b, S, di), f32),
        _spec(one_chip, (b, S, N), f32), _spec(one_chip, (b, S, N), f32),
        _spec(one_chip, (di, N), f32))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_full_width_jamba_step_fits_one_v5e(one_chip, monkeypatch):
    """The jamba2-3b cell's step (16 records of 694,400 B) as the chip
    runs it: the scan kernel in every Mamba layer, the fused attention in
    the two attention layers."""
    from repro.kernels.compat import INTERPRET_ENV
    from repro.models import get_model
    from repro.perception import build_step, resolve_config

    monkeypatch.setenv(INTERPRET_ENV, "0")
    cfg = resolve_config("jamba2-3b")
    shapes = jax.eval_shape(get_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), shapes)
    step = build_step(cfg, out_features=16, metrics=False, donate=False,
                      interpret=False)
    text = step.lower(params, *_batch_specs(
        one_chip, 16, 694_400, False)).compile().as_text()
    # the decode kernel, one scan kernel (named after its scope) in the
    # loop body of each of the three runs of Mamba layers, and the two
    # attention layers' kernels
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert len(re.findall(r"%ssm_scan[.\d]* = ", text)) == 3
    assert len(re.findall(r"%_flash_attention[.\d]* = ", text)) == 2
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 3.0e9 < n_params < 3.1e9
