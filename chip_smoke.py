"""Smoke test of the batched replay path on one TPU chip.

    python chip_smoke.py

Drives the main path once through its user entry points: a thread-backend
``ScenarioSuite`` replays a seeded bag of 256 camera/lidar records
(81,920 bytes each: 32 tokens of qwen3-4b's d_model = 2560) through
``perception://qwen3-4b`` at its published widths, with random bf16
weights from a fixed seed, next to a batched pass-through scenario whose
fused Pallas metrics sink runs on its own.  The suite runs twice: the
clean run's outputs become the golden bags, and the rerun must PASS with
bit-identical output images.

The chip's results are then checked against references off the Pallas
path:

* the fused kernel's record digests equal ``record_digests_np`` exactly,
  and so do the suite's topic checksums;
* the decoded features match ``sensor_decode_reference`` (rtol = atol =
  1e-6, float32);
* the step's logits match the same bf16 forward over reference-decoded
  features to within 2 % of the largest reference logit: both programs
  round to bf16 at every layer, and the two compilations may fuse and
  order those roundings differently over 36 layers;
* the compiled step contains a ``tpu_custom_call`` (the Pallas kernel ran
  compiled, not interpreted).

Any failed phase exits non-zero.  Only a full pass prints the last line,
``{"ok": true, "device": {...}}``.  Without a TPU (``JAX_PLATFORMS=cpu``,
or a TPU that failed to initialise) it exits 1 naming the platform found.
The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<repo>/.jax_cache`` (see ``repro.compile_cache``).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import NoReturn

SEED = 0
MODEL = "qwen3-4b"
REF = "perception://" + MODEL
TOPICS = ("/camera", "/lidar")
RELAY = "/relay"                # pass-through scenario's topic prefix
N_RECORDS = 256
RECORD_BYTES = 81920            # 32 tokens x d_model 2560
BATCH = 64
PERIOD_NS = 50_000_000          # interleaved topics, 10 Hz each
LOGIT_TOL = 0.02                # of the largest reference logit
STEADY_REPS = 5


def fail(msg: str) -> NoReturn:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


def low32(ts):
    """Timestamps mod 2**32, as the record digest mixes them in."""
    import numpy as np
    return (np.asarray(ts).astype(np.uint64)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def passthrough(msgs):
    """Batched identity logic: republishes each record unchanged under
    ``RELAY + topic`` (a new topic, so outputs do not loop back into the
    logic), so the fused metrics sink's checksums of the relayed topics
    can be checked against the input bag."""
    return [(RELAY + m.topic, m.timestamp, m.data) for m in msgs]


def logic_batches(msgs):
    """The batches a batched logic is handed: the player publishes
    ``BATCH``-record windows, and the bus delivers each window to a
    topic's subscriber as that topic's records only."""
    groups = []
    for lo in range(0, len(msgs), BATCH):
        for topic in TOPICS:
            groups.append([m for m in msgs[lo:lo + BATCH]
                           if m.topic == topic])
    return groups


def write_bag(path: str):
    import numpy as np
    from repro.core import Bag
    rng = np.random.default_rng(SEED)
    payload = rng.integers(0, 256, (N_RECORDS, RECORD_BYTES), dtype=np.uint8)
    bag = Bag.open_write(path, chunk_bytes=4 * RECORD_BYTES)
    for i in range(N_RECORDS):
        bag.write(TOPICS[i % len(TOPICS)], i * PERIOD_NS, payload[i].tobytes())
    bag.close()
    return payload


def run_suite(bag_path: str, goldens=None):
    from repro.core import Scenario, ScenarioSuite
    goldens = goldens or {}
    scenarios = [
        Scenario("perc", bag_path, REF, batch_size=BATCH, num_partitions=1,
                 golden_bag_path=goldens.get("perc")),
        Scenario("pass", bag_path, passthrough, batch_size=BATCH,
                 num_partitions=1, golden_bag_path=goldens.get("pass")),
    ]
    t0 = time.perf_counter()
    verdicts = ScenarioSuite(scenarios, num_workers=2,
                             backend="thread").run(timeout=900)
    wall = time.perf_counter() - t0
    for name, v in verdicts.items():
        check(v.status == "PASS", v.summary())
        check(v.report.messages_out == N_RECORDS,
              f"{name}: {v.report.messages_out} outputs, want {N_RECORDS}")
    return verdicts, wall


def main() -> int:
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")

    import numpy as np
    from repro.core.aggregation import combine_digests, record_digests_np
    from repro.core import Message
    from repro.data.pipeline import assemble_message_batch
    from repro.kernels.compat import INTERPRET_ENV, resolve_interpret
    from repro.kernels.ref import sensor_decode_reference
    from repro.kernels.sensor_decode import (sensor_decode,
                                            sensor_decode_metrics)
    from repro.perception import features_to_logits, get_step

    check(INTERPRET_ENV not in os.environ,
          f"{INTERPRET_ENV} is set; the smoke runs compiled kernels only")
    check(resolve_interpret(None) is False,
          "kernels resolve to interpret mode on this device")
    print(f"device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    print(f"compile cache: {cache_dir}")

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        bag_path = os.path.join(tmp, "drive.bag")
        payload = write_bag(bag_path)

        t0 = time.perf_counter()
        step = get_step(REF)
        jax.block_until_ready(step.params)
        print(f"param init (compile + run): "
              f"{time.perf_counter() - t0:.3f} s")
        check(step.cfg.d_model == 2560 and step.cfg.num_layers == 36,
              f"{REF} did not build the published config: {step.cfg}")
        check(step.interpret is False, "perception step resolved interpret")

        clean, wall = run_suite(bag_path)
        print(f"suite run 1 (clean, compiles the step): {wall:.3f} s")
        goldens = {}
        for name, v in clean.items():
            goldens[name] = os.path.join(tmp, f"golden-{name}.bag")
            with open(goldens[name], "wb") as f:
                f.write(v.report.output_image)
        rerun, wall = run_suite(bag_path, goldens)
        print(f"suite run 2 (against golden): {wall:.3f} s")
        for name in clean:
            check(rerun[name].report.output_image
                  == clean[name].report.output_image,
                  f"{name}: output image differs between runs")

        # -- references off the Pallas path --------------------------------
        ts = np.arange(N_RECORDS, dtype=np.int64) * PERIOD_NS
        ts_low = low32(ts)
        lengths = np.full(N_RECORDS, RECORD_BYTES, np.int32)
        want_digests = record_digests_np(payload, lengths, ts_low)
        for i, topic in enumerate(TOPICS):
            want = combine_digests(want_digests[i::len(TOPICS)])
            got = clean["pass"].metrics[RELAY + topic].checksum
            check(got == want,
                  f"sink checksum of {RELAY + topic}: {got} != {want}")

        msgs = [Message(TOPICS[i % len(TOPICS)], int(ts[i]),
                        payload[i].tobytes()) for i in range(N_RECORDS)]
        groups = logic_batches(msgs)
        out_digests = []
        for group in groups:
            out = step.run_batch(assemble_message_batch(group))
            out_digests.append(record_digests_np(
                out["payload"], out["lengths"], low32(out["timestamps"])))
        got = clean["perc"].metrics[step.out_topic].checksum
        want = combine_digests(np.concatenate(out_digests))
        check(got == want, f"perception checksum: {got} != {want}")

        # the step's own batch shape: the first window's camera records
        batch = assemble_message_batch(groups[0])
        rows = len(groups[0])
        first = slice(0, len(TOPICS) * rows, len(TOPICS))  # its bag indices
        dev_args = [jax.numpy.asarray(batch[k]) for k in
                    ("payload", "scale", "zero_point", "lengths")]
        ref_feats = sensor_decode_reference(*dev_args)
        fused = sensor_decode_metrics(*dev_args,
                                      jax.numpy.asarray(ts_low[first]))
        check(np.array_equal(np.asarray(fused["record_digests"]),
                             want_digests[first]),
              "kernel record digests differ from record_digests_np")
        for name, feats in (("sensor_decode", sensor_decode(*dev_args)),
                            ("sensor_decode_metrics", fused["features"])):
            check(np.allclose(np.asarray(feats), np.asarray(ref_feats),
                              rtol=1e-6, atol=1e-6),
                  f"{name} features differ from sensor_decode_reference")

        logits = np.asarray(step.step_arrays(batch)[0])
        ref_logits = np.asarray(jax.jit(
            lambda p, f: features_to_logits(step.cfg, p, f,
                                            step.out_features))(
            step.params, ref_feats))
        check(logits.shape == (rows, step.out_features),
              f"logits shape {logits.shape}")
        check(np.isfinite(logits).all(), "non-finite logits")
        err = float(np.abs(logits - ref_logits).max())
        scale = float(np.abs(ref_logits).max())
        print(f"logits vs reference forward: max |diff| {err!r} "
              f"(largest reference logit {scale!r})")
        check(err <= LOGIT_TOL * max(scale, 1.0),
              f"logits differ from the reference forward by {err}")

        hlo = step._step.lower(step.params, *dev_args).compile().as_text()
        check("tpu_custom_call" in hlo, "compiled step has no Pallas kernel")

        times = []
        for _ in range(STEADY_REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(step.step_arrays(batch))
            times.append(time.perf_counter() - t0)
        print(f"steady batch ({rows} x {RECORD_BYTES} B): "
              f"min {min(times)!r} s, all {times!r}")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
