"""Zero-copy device path: message-path vs zero-copy vs zero-copy+jitted
forward (ISSUE 6 tentpole).

The same stream of DATA frames is consumed three ways, each folding the
identical per-topic metric state:

  * **message**   — ``decode_data`` materialises per-message ``Message``
    objects, ``assemble_message_batch`` re-packs them row by row, digests
    via ``record_digests_np`` (the pre-existing replay path),
  * **zerocopy**  — ``frame_to_batch`` reinterprets the frame's columnar
    body as the batch dict directly (payload matrix is a reshape *view*
    of the frame bytes for uniform aligned records), digests via the same
    numpy engine, folded with ``accumulate_topic_state_arrays``,
  * **device**    — ``frame_to_batch`` feeds a
    :class:`repro.perception.PerceptionStep` with ``metrics=True``: ONE
    jitted program runs the Pallas decode+digest sweep and the model
    forward with donated batch buffers; input digests come off the kernel
    digest plane (cross-engine bit-parity asserted).

All three runs must fold bit-identical per-topic input checksums
(asserted, untimed).  A second untimed phase runs a
``perception://<model>`` scenario suite twice (clean -> golden -> PASS)
and replays the same stream through the zero-copy face, asserting the
output-topic metrics are bit-identical to the suite verdict's — the
acceptance gate of the device path.

Emits CSV rows plus machine-readable ``BENCH_perception.json``.
``--check`` re-reads the JSON and exits non-zero if the zero-copy path
fell below ``MIN_RATIO``x the message path, or any bit-parity assertion
was not recorded — the CI gate.

    PYTHONPATH=src python -m benchmarks.perception [--check]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from repro.core import Message, Scenario, ScenarioSuite
from repro.core.aggregation import (accumulate_topic_state,
                                    accumulate_topic_state_arrays,
                                    finalize_topic_state, record_digests_np)
from repro.data.pipeline import assemble_message_batch
from repro.net.wire import decode_data, encode_data, frame_to_batch

N_MSGS = 20000
PAYLOAD_BYTES = 256
TOPICS = ("/camera", "/lidar")
FRAME_BATCH = 512          # messages per DATA frame (device batch rows)
REPEATS = 3
MODEL = "qwen3-4b-tiny"    # 256 B records are narrower than d_model=2560
SUITE_MSGS = 1024          # verdict-phase stream (two full model sweeps)
SUITE_BATCH = 128
#: CI gate: the zero-copy frame->batch path must beat the per-message
#: decode+assemble path by at least this factor at 256 B payloads
MIN_RATIO = 1.3

JSON_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, "BENCH_perception.json")


def _make_messages(n: int = N_MSGS, seed: int = 13) -> list[Message]:
    rng = np.random.RandomState(seed)
    return [Message(TOPICS[i % len(TOPICS)], i * 1000,
                    rng.bytes(PAYLOAD_BYTES))
            for i in range(n)]


def _make_frames(msgs: list[Message],
                 batch: int = FRAME_BATCH) -> list[bytes]:
    return [encode_data(msgs[lo:lo + batch])
            for lo in range(0, len(msgs), batch)]


def _ts_low(ts: np.ndarray) -> np.ndarray:
    return (np.asarray(ts).astype(np.uint64)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _sums(state: dict) -> dict[str, int]:
    return {t: m.checksum for t, m in finalize_topic_state(state).items()}


def _run_message(frames: list[bytes],
                 verify: bool = False) -> tuple[float, Optional[dict]]:
    """Baseline: per-message objects, then per-row batch re-assembly."""
    state: dict = {}
    t0 = time.perf_counter()
    for body in frames:
        msgs = decode_data(body)
        arrays = assemble_message_batch(msgs)
        digests = record_digests_np(arrays["payload"], arrays["lengths"],
                                    _ts_low(arrays["timestamps"]))
        accumulate_topic_state(state, msgs, arrays, digests)
    wall = time.perf_counter() - t0
    return wall, _sums(state) if verify else None


def _run_zerocopy(frames: list[bytes],
                  verify: bool = False) -> tuple[float, Optional[dict]]:
    """Frame columns ARE the batch: no Message objects, no row copies."""
    state: dict = {}
    t0 = time.perf_counter()
    for body in frames:
        batch = frame_to_batch(body)
        digests = record_digests_np(batch["payload"], batch["lengths"],
                                    _ts_low(batch["timestamps"]))
        accumulate_topic_state_arrays(state, batch, digests)
    wall = time.perf_counter() - t0
    return wall, _sums(state) if verify else None


def _run_device(step, frames: list[bytes],
                verify: bool = False) -> tuple[float, Optional[dict]]:
    """Zero-copy feed into the fused decode->forward jit; input digests
    ride the Pallas digest plane of the same compiled program."""
    state: dict = {}
    t0 = time.perf_counter()
    for body in frames:
        batch = frame_to_batch(body)
        out = step.run_batch(batch)
        accumulate_topic_state_arrays(state, batch,
                                      out["input_record_digests"])
    wall = time.perf_counter() - t0
    return wall, _sums(state) if verify else None


def _best_of_pair(fa, fb, repeats: int = REPEATS):
    """Interleaved best-of (see benchmarks/pipeline.py): alternating
    repeats see the same clock/cache conditions, so drift never lands on
    only one contestant."""
    best_a = best_b = None
    for _ in range(repeats):
        ra = fa()
        if best_a is None or ra[0] < best_a[0]:
            best_a = ra
        rb = fb()
        if best_b is None or rb[0] < best_b[0]:
            best_b = rb
    return best_a, best_b


def _verdict_parity(tmpdir: str) -> dict:
    """Run a ``perception://`` suite twice (clean -> golden -> PASS) and a
    zero-copy replay of the same stream; output-topic metrics must be
    bit-identical across the Message-contract and columnar faces."""
    from repro.perception import get_step

    msgs = _make_messages(SUITE_MSGS, seed=29)
    bag_path = os.path.join(tmpdir, "suite.bag")
    from repro.core import Bag
    bag = Bag.open_write(bag_path, chunk_bytes=32 * 1024)
    for m in msgs:
        bag.write(m.topic, m.timestamp, m.data)
    bag.close()

    def scenario(golden: Optional[str] = None) -> Scenario:
        return Scenario("perception", bag_path,
                        user_logic="perception://" + MODEL,
                        batch_size=SUITE_BATCH, num_partitions=1,
                        golden_bag_path=golden)

    clean = ScenarioSuite([scenario()], num_workers=1).run(
        timeout=600)["perception"]
    assert clean.passed and not clean.vacuous
    golden = os.path.join(tmpdir, "golden.bag")
    with open(golden, "wb") as f:
        f.write(clean.report.output_image)
    rerun = ScenarioSuite([scenario(golden)], num_workers=1).run(
        timeout=600)["perception"]
    assert rerun.status == "PASS", rerun.summary()

    # zero-copy replay: same stream, same batch split, same cached step
    # the suite's logic ref resolves to — logits must be bit-identical
    step = get_step("perception://" + MODEL)
    state: dict = {}
    for body in _make_frames(msgs, SUITE_BATCH):
        out = step.run_batch(frame_to_batch(body))
        digests = record_digests_np(out["payload"], out["lengths"],
                                    _ts_low(out["timestamps"]))
        accumulate_topic_state_arrays(state, out, digests)
    zc = finalize_topic_state(state, sort=True)
    golden_metrics = rerun.metrics
    assert set(zc) == set(golden_metrics)
    for topic in zc:
        assert zc[topic] == golden_metrics[topic], topic
    return {
        "clean_status": clean.status, "golden_status": rerun.status,
        "output_checksums": {t: int(m.checksum) for t, m in zc.items()},
        "output_metrics_identical": True,
    }


def run_race() -> dict:
    from repro.perception import PerceptionStep

    msgs = _make_messages()
    frames = _make_frames(msgs)
    step = PerceptionStep(MODEL, metrics=True)

    # bit-parity verification first (untimed; also warms the jit trace):
    # three consumers, one digest algebra, identical folds
    _, msg_sums = _run_message(frames, verify=True)
    _, zc_sums = _run_zerocopy(frames, verify=True)
    _, dev_sums = _run_device(step, frames, verify=True)
    assert msg_sums == zc_sums, "zero-copy batch changed checksums"
    assert msg_sums == dev_sums, "kernel digest plane changed checksums"

    # the race proper: pure timed runs, interleaved best-of
    (msg_s, _), (zc_s, _) = _best_of_pair(
        lambda: _run_message(frames),
        lambda: _run_zerocopy(frames))
    dev_s = min(_run_device(step, frames)[0] for _ in range(REPEATS))

    with tempfile.TemporaryDirectory(prefix="repro-bench-") as d:
        verdicts = _verdict_parity(d)

    return {
        "bench": "perception", "model": MODEL,
        "messages": N_MSGS, "payload_bytes": PAYLOAD_BYTES,
        "frame_batch": FRAME_BATCH, "min_ratio": MIN_RATIO,
        "message_wall_s": msg_s, "zerocopy_wall_s": zc_s,
        "device_wall_s": dev_s,
        "message_msgs_per_s": N_MSGS / msg_s,
        "zerocopy_msgs_per_s": N_MSGS / zc_s,
        "device_msgs_per_s": N_MSGS / dev_s,
        "zerocopy_vs_message_ratio": msg_s / zc_s,
        "device_vs_message_ratio": msg_s / dev_s,
        "checksums_identical": True,
        "checksums": {t: int(c) for t, c in zc_sums.items()},
        **verdicts,
    }


def main(csv: bool = True, json_path: str = JSON_PATH) -> list[tuple]:
    payload = run_race()
    rows = [
        ("perception_message_path",
         payload["message_wall_s"] * 1e6 / N_MSGS,
         f"{payload['message_msgs_per_s']:.0f} msg/s "
         "(decode_data + assemble_message_batch)"),
        ("perception_zerocopy_path",
         payload["zerocopy_wall_s"] * 1e6 / N_MSGS,
         f"{payload['zerocopy_msgs_per_s']:.0f} msg/s (frame_to_batch)"),
        ("perception_device_path",
         payload["device_wall_s"] * 1e6 / N_MSGS,
         f"{payload['device_msgs_per_s']:.0f} msg/s "
         "(fused decode+digests+forward, donated buffers)"),
        ("perception_zerocopy_vs_message_ratio",
         payload["zerocopy_vs_message_ratio"],
         "checksums + suite verdicts bit-identical"),
    ]
    if csv:
        for name, val, derived in rows[:3]:
            print(f"{name},{val:.2f},{derived}")
        print(f"{rows[3][0]},{rows[3][1]:.2f}x,{rows[3][2]}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
    return rows


def check(json_path: str = JSON_PATH) -> int:
    """CI gate: fail (exit 1) when the zero-copy path regressed below
    ``MIN_RATIO``x the message path, or bit-parity was not upheld."""
    with open(json_path) as f:
        payload = json.load(f)
    ratio = payload["zerocopy_vs_message_ratio"]
    print(f"zerocopy {payload['zerocopy_msgs_per_s']:.0f} msg/s vs message "
          f"{payload['message_msgs_per_s']:.0f} msg/s -> {ratio:.2f}x "
          f"(gate {payload.get('min_ratio', MIN_RATIO)}x); device "
          f"{payload['device_msgs_per_s']:.0f} msg/s")
    if not payload.get("checksums_identical") \
            or not payload.get("output_metrics_identical") \
            or payload.get("golden_status") != "PASS":
        print("FAIL: device path is not bit-identical to the message path",
              file=sys.stderr)
        return 1
    if ratio < payload.get("min_ratio", MIN_RATIO):
        print("FAIL: zero-copy path regressed below the message-path "
              "speedup gate", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--check" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--check"]
        sys.exit(check(args[0] if args else JSON_PATH))
    main()
