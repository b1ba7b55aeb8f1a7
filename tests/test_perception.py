"""Zero-copy device path (ISSUE 6): frame<->batch reinterpretation parity,
cross-backend/carrier checksum parity, the fused decode->forward perception
step (donation, determinism, scenario integration), and the
``REPRO_PALLAS_INTERPRET`` plumbing.

User-logic functions are module-level so they cross the process-backend
pickle boundary.
"""

import os
import warnings

import numpy as np
import pytest

from repro.configs import ALL_ARCHS
from repro.core import Bag, Message, Scenario, ScenarioSuite
from repro.core.aggregation import (accumulate_topic_state_arrays,
                                    finalize_topic_state, record_digests_np)
from repro.data.pipeline import assemble_message_batch, batch_from_columns
from repro.net.wire import (WireError, batch_to_frame, decode_data,
                            encode_data, frame_to_batch)

TOPICS = ("/camera", "/lidar")
#: CPU-sized perception model: the reduced same-structure qwen3-4b config
TINY = "qwen3-4b-tiny"


def _msgs(n=100, payload=256, seed=0, topics=TOPICS):
    rng = np.random.RandomState(seed)
    return [Message(topics[i % len(topics)], i * 1000 + 7,
                    rng.bytes(payload if isinstance(payload, int)
                              else int(payload[i % len(payload)])))
            for i in range(n)]


def _ts_low(ts):
    return (np.asarray(ts).astype(np.uint64)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _fold_frames(frames):
    """Zero-copy metric fold: the reference the backend/carrier runs must
    reproduce bit for bit."""
    state = {}
    for body in frames:
        batch = frame_to_batch(body)
        digests = record_digests_np(batch["payload"], batch["lengths"],
                                    _ts_low(batch["timestamps"]))
        accumulate_topic_state_arrays(state, batch, digests)
    return {t: m.checksum
            for t, m in finalize_topic_state(state, sort=True).items()}


# -- frame <-> batch reinterpretation ----------------------------------------


def test_frame_to_batch_matches_message_path_uniform():
    msgs = _msgs(64, payload=256)
    body = encode_data(msgs)
    via_msgs = assemble_message_batch(decode_data(body))
    batch = frame_to_batch(body)
    for key in via_msgs:
        assert np.array_equal(batch[key], via_msgs[key]), key
        assert batch[key].dtype == via_msgs[key].dtype, key
    assert batch["topics"] == tuple(dict.fromkeys(m.topic for m in msgs))
    assert [batch["topics"][j] for j in batch["topic_idx"]] \
        == [m.topic for m in msgs]
    # uniform aligned payloads: the matrix is a VIEW of the frame bytes
    assert batch["payload"].base is not None


def test_frame_to_batch_matches_message_path_ragged():
    msgs = _msgs(50, payload=(3, 129, 256, 77, 1), seed=2)
    body = encode_data(msgs)
    via_msgs = assemble_message_batch(decode_data(body))
    batch = frame_to_batch(body)
    for key in via_msgs:
        assert np.array_equal(batch[key], via_msgs[key]), key


def test_batch_to_frame_roundtrip_is_byte_exact():
    for payload in (256, (3, 129, 256, 77, 1)):
        body = encode_data(_msgs(40, payload=payload, seed=3))
        assert batch_to_frame(frame_to_batch(body)) == body
    # and from a host-built columnar batch too
    batch = batch_from_columns(
        ["/a", "/b"], [0, 1, 0], [10, 20, 30], [4, 4, 4],
        np.arange(12, dtype=np.uint8))
    assert np.array_equal(frame_to_batch(batch_to_frame(batch))["payload"],
                          batch["payload"])


def test_frame_to_batch_rejects_corrupt_and_empty_frames():
    import struct
    body = encode_data(_msgs(8))
    with pytest.raises(WireError, match="corrupt"):
        frame_to_batch(body[:-3])               # truncated payload column
    (head_len,) = struct.unpack_from("<I", body, 4)
    bad = bytearray(body)
    bad[8 + head_len] = 99                      # topic_idx[0] out of table
    with pytest.raises(WireError, match="corrupt"):
        frame_to_batch(bytes(bad))
    with pytest.raises(WireError, match="empty"):
        frame_to_batch(encode_data([]))


# -- cross-backend / cross-carrier checksum parity ---------------------------


def prov_logic(msg):
    return ("/det" + msg.topic, msg.data[:16])


def cons_logic(msg):
    return ("/score", bytes(reversed(msg.data)))


@pytest.mark.parametrize("backend", ["thread", "process"])
@pytest.mark.parametrize("carrier", ["inline", "wire"])
def test_zero_copy_checksums_match_suite(tmp_path, backend, carrier):
    """The zero-copy frame fold must reproduce, bit for bit, the output
    checksums of a provider->consumer suite on every backend x carrier."""
    msgs = _msgs(120, payload=64, seed=9)
    bag_path = str(tmp_path / "in.bag")
    bag = Bag.open_write(bag_path, chunk_bytes=2048)
    for m in msgs:
        bag.write(m.topic, m.timestamp, m.data)
    bag.close()

    v = ScenarioSuite(
        [Scenario("provider", bag_path, prov_logic,
                  exports=("/det/camera", "/det/lidar")),
         Scenario("consumer", bag_path, cons_logic,
                  imports=("/det/camera", "/det/lidar"))],
        num_workers=2, backend=backend,
        export_transport=carrier).run(timeout=300)
    suite_sums = {}
    for verdict in v.values():
        suite_sums.update(
            {t: m.checksum for t, m in verdict.metrics.items()})

    det = [Message("/det" + m.topic, m.timestamp, m.data[:16])
           for m in msgs]
    score = [Message("/score", m.timestamp, bytes(reversed(m.data)))
             for m in msgs + det]
    expect = _fold_frames([encode_data(det[:70]), encode_data(det[70:]),
                           encode_data(score)])
    assert suite_sums == expect


# -- PerceptionStep ----------------------------------------------------------


def test_perception_step_message_vs_zero_copy_parity():
    from repro.perception import PerceptionStep

    msgs = _msgs(24, payload=256, seed=4)
    step = PerceptionStep(TINY, metrics=True, donate=False)
    out = step.run_batch(frame_to_batch(encode_data(msgs)))
    via_msgs = step(msgs)
    assert [t for t, _, _ in via_msgs] == [step.out_topic] * len(msgs)
    assert [ts for _, ts, _ in via_msgs] == [m.timestamp for m in msgs]
    assert [d for _, _, d in via_msgs] \
        == [out["payload"][i].tobytes() for i in range(len(msgs))]
    # kernel digest plane == numpy digest engine (cross-engine parity)
    batch = frame_to_batch(encode_data(msgs))
    expect = record_digests_np(batch["payload"], batch["lengths"],
                               _ts_low(batch["timestamps"]))
    assert np.array_equal(out["input_record_digests"], expect)
    # deterministic in (model, seed): a fresh step reproduces the bytes
    again = PerceptionStep(TINY, metrics=True, donate=False)
    out2 = again.run_batch(frame_to_batch(encode_data(msgs)))
    assert np.array_equal(out2["payload"], out["payload"])


def test_perception_step_output_batch_feeds_wire_and_metrics():
    from repro.perception import PerceptionStep

    msgs = _msgs(16, payload=128, seed=5)
    step = PerceptionStep(TINY, donate=False)
    out = step.run_batch(frame_to_batch(encode_data(msgs)))
    assert out["payload"].shape == (16, 4 * step.out_features)
    assert out["topics"] == (step.out_topic,)
    # the output batch is itself frameable (zero-copy republish)
    rt = frame_to_batch(batch_to_frame(out))
    assert np.array_equal(rt["payload"][:, :out["payload"].shape[1]],
                          out["payload"])
    assert rt["topics"] == (step.out_topic,)


def test_perception_step_donates_and_is_silent():
    """Donation semantics: a shape/dtype-matched donated buffer is reused
    in place (pointer equality) and invalidated; the perception step's
    donated-but-unusable batch buffers never touch the caller's numpy
    memory, and the shape-mismatch donation warning is suppressed at the
    call site."""
    import jax
    import jax.numpy as jnp
    from repro.perception import PerceptionStep

    # where the backend aliases donated buffers, the output reuses the
    # input allocation (shape/dtype-matched probe) and the input dies
    probe = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    x = jnp.arange(4096, dtype=jnp.float32)
    if not hasattr(x, "unsafe_buffer_pointer"):
        pytest.skip("backend exposes no buffer pointers")
    ptr = x.unsafe_buffer_pointer()
    y = probe(x)
    assert x.is_deleted()
    assert y.unsafe_buffer_pointer() == ptr

    # the step donates its device-side batch copies, never the caller's
    # numpy batch: the frame view must be readable after the call
    donating = PerceptionStep(TINY, donate=True)
    msgs = _msgs(8, payload=128, seed=6)
    batch = frame_to_batch(encode_data(msgs))
    before = batch["payload"].copy()
    with warnings.catch_warnings(record=True) as caught:
        # step_arrays must not leak the "not usable" warning to callers
        warnings.simplefilter("always")
        logits, _ = donating.step_arrays(batch)
    assert not [w for w in caught if "donated" in str(w.message)]
    assert np.array_equal(batch["payload"], before)
    assert np.asarray(logits).shape == (8, donating.out_features)

    # donate=False keeps even device-side inputs alive
    step = PerceptionStep(TINY, donate=False)
    kept = jnp.zeros((8, 128), jnp.uint8)
    step._step(step.params, kept, jnp.full(8, 1 / 255, jnp.float32),
               jnp.zeros(8, jnp.float32), jnp.full(8, 128, jnp.int32))
    assert not kept.is_deleted()


#: every registered decoder-only config: the step feeds embeddings alone
DECODERS = [a for a in ALL_ARCHS + ["jamba2-3b"]
            if a != "seamless-m4t-large-v2"]


@pytest.mark.parametrize("arch", DECODERS)
def test_every_decoder_config_runs_the_step(arch):
    """``PerceptionStep("<arch>-tiny")`` agrees with the model forward over
    features decoded off the Pallas path."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import sensor_decode_reference
    from repro.perception import PerceptionStep, features_to_logits

    step = PerceptionStep(arch + "-tiny", donate=False)
    batch = frame_to_batch(encode_data(_msgs(8, payload=256, seed=8)))
    got = np.asarray(step.step_arrays(batch)[0])
    feats = sensor_decode_reference(*(jnp.asarray(batch[k]) for k in (
        "payload", "scale", "zero_point", "lengths")))
    want = np.asarray(jax.jit(lambda p, f: features_to_logits(
        step.cfg, p, f, step.out_features))(step.params, feats))
    assert got.shape == (8, step.out_features) and np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


# -- Scenario integration ----------------------------------------------------


def _perception_bag(tmp_path, n=64, payload=128):
    path = str(tmp_path / "sensors.bag")
    bag = Bag.open_write(path, chunk_bytes=4096)
    for m in _msgs(n, payload=payload, seed=7):
        bag.write(m.topic, m.timestamp, m.data)
    bag.close()
    return path


def test_perception_scheme_runs_as_batched_logic(tmp_path):
    from repro.perception import get_step

    bag_path = _perception_bag(tmp_path)
    sc = Scenario("perc", bag_path, "perception://" + TINY,
                  batch_size=16, num_partitions=1)
    a = ScenarioSuite([sc], num_workers=1).run(timeout=300)["perc"]
    b = ScenarioSuite([sc], num_workers=1).run(timeout=300)["perc"]
    assert a.passed and not a.vacuous
    assert a.report.messages_out == 64
    assert list(a.metrics) == [get_step("perception://" + TINY).out_topic]
    # jitted replay is deterministic: bit-identical output images
    assert a.report.output_image == b.report.output_image


def test_perception_scheme_runs_interleaved_jamba(tmp_path):
    """``perception://jamba2-3b-tiny``: a whole period of the interleaved
    stack (ssm, attention, ssm, ssm) as batched logic; the verdict
    manifest counts which scan each trace of the SSM took (the jnp scan on
    the CPU)."""
    import json

    from repro.models import ssm as SSM
    from repro.perception import _STEPS, get_step

    # the counters are the process's: read what this suite adds
    before = {n: SSM._PATHS.counter(n).value for n in ("jnp", "kernel")}
    ref = "perception://jamba2-3b-tiny"
    _STEPS.pop("jamba2-3b-tiny", None)
    step = get_step(ref)
    assert step.cfg.layer_kinds == ("ssm", "attention", "ssm", "ssm")
    bag_path = _perception_bag(tmp_path, n=32, payload=256)
    log = str(tmp_path / "verdicts.jsonl")
    sc = Scenario("jamba", bag_path, ref, batch_size=16, num_partitions=1)
    v = ScenarioSuite([sc], num_workers=1).run(
        timeout=300, verdict_log=log)["jamba"]
    assert v.passed and not v.vacuous
    assert v.report.messages_out == 32
    counts = json.load(open(log + ".manifest.json"))["metrics"]["ssm"]
    # one trace a run of Mamba layers: ssm, then ssm, ssm
    assert counts["jnp"] - before["jnp"] == 2
    assert counts.get("kernel", 0) == before["kernel"]


def test_perception_scheme_requires_batch_size_and_thread_backend(tmp_path):
    bag_path = _perception_bag(tmp_path, n=8)
    with pytest.raises(ValueError, match="batch_size"):
        Scenario("perc", bag_path, "perception://" + TINY)
    sc = Scenario("perc", bag_path, "perception://" + TINY, batch_size=8)
    with pytest.raises(ValueError, match="thread backend"):
        ScenarioSuite([sc], backend="process").run(timeout=60)


# -- model-name resolution ---------------------------------------------------


def test_perception_tiny_name_builds_tiny_config():
    from repro.configs.tiny import tiny_config
    from repro.perception import get_step

    step = get_step("perception://" + TINY)
    assert step.cfg == tiny_config("qwen3-4b")
    assert step.cfg.name == TINY
    assert step.cfg.d_model == 64 and step.cfg.num_layers == 2


def test_perception_published_name_resolves_full_width():
    """``perception://qwen3-4b`` names the published widths.  Checked on
    the config and on shapes only: nothing is initialised."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    from repro.perception import build_step, resolve_config

    cfg = resolve_config("qwen3-4b")
    assert (cfg.name, cfg.d_model, cfg.num_layers) == ("qwen3-4b", 2560, 36)
    assert cfg.dtype == "bfloat16"
    params = jax.eval_shape(get_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(params)
    assert {p.dtype for p in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert 4.0e9 < sum(p.size for p in leaves) < 4.5e9
    # one 32-token row of 81,920 bytes through the step, abstractly
    step = build_step(cfg, out_features=16, metrics=True, donate=False,
                      interpret=True)
    R, Nb = 2, 32 * cfg.d_model
    logits, digests = jax.eval_shape(
        step, params, jax.ShapeDtypeStruct((R, Nb), jnp.uint8),
        *(jax.ShapeDtypeStruct((R,), dt) for dt in
          (jnp.float32, jnp.float32, jnp.int32, jnp.uint32)))
    assert (logits.shape, logits.dtype) == ((R, 16), jnp.float32)
    assert (digests.shape, digests.dtype) == ((R,), jnp.uint32)


# -- REPRO_PALLAS_INTERPRET plumbing -----------------------------------------


def test_resolve_interpret_env_and_override(monkeypatch):
    from repro.kernels.compat import INTERPRET_ENV, resolve_interpret

    monkeypatch.delenv(INTERPRET_ENV, raising=False)
    import jax
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    for raw, want in (("1", True), ("true", True), ("on", True),
                      ("0", False), ("false", False), ("off", False),
                      ("No", False), ("yes", True)):
        monkeypatch.setenv(INTERPRET_ENV, raw)
        assert resolve_interpret(None) is want, raw
    # an explicit argument always wins over the environment
    monkeypatch.setenv(INTERPRET_ENV, "0")
    assert resolve_interpret(True) is True
    monkeypatch.setenv(INTERPRET_ENV, "1")
    assert resolve_interpret(False) is False
    monkeypatch.setenv(INTERPRET_ENV, "   ")    # blank = unset
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")


def test_kernel_entry_points_honor_interpret_env(monkeypatch):
    """Every kernel wrapper resolves interpret=None through the env knob
    at call time (not frozen at import/trace time)."""
    from repro.kernels import compat
    from repro.kernels.sensor_decode import sensor_decode

    calls = []
    real = compat.resolve_interpret

    def spy(interpret=None):
        calls.append(interpret)
        return real(interpret)

    import repro.kernels.sensor_decode as sd
    monkeypatch.setattr(sd, "resolve_interpret", spy)
    payload = np.zeros((4, 128), np.uint8)
    scale = np.full(4, 1 / 255, np.float32)
    zp = np.zeros(4, np.float32)
    lengths = np.full(4, 128, np.int32)
    monkeypatch.setenv(compat.INTERPRET_ENV, "1")
    out = sensor_decode(payload, scale, zp, lengths)
    assert out.shape == (4, 128)
    assert calls == [None]
