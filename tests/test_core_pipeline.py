"""Staged replay pipeline + queued MessageBus semantics (ISSUE 4).

Covers: per-topic FIFO order under backpressure, the drain()/stop()
end-of-replay barrier, slow-subscriber overlap actually beating the
synchronous shape on wall clock, bit-identical verdicts/checksums between
sync and queued modes, the double-subscribe fix, deferred callback-error
propagation, spill-aware aggregate dispatch, and verdict persistence
(JSONL log + suite manifest).
"""

import json
import threading
import time

import numpy as np
import pytest

from repro.core import (Bag, Message, MessageBus, ProcessBackend, RosPlay,
                        RosRecord, Scenario, ScenarioSuite)

TOPICS = ("/camera", "/lidar", "/imu")


def _make_bag(path, n=600, topics=TOPICS, payload=64):
    b = Bag.open_write(path, chunk_bytes=4096)
    rng = np.random.RandomState(0)
    for i in range(n):
        t = topics[i % len(topics)]
        ts = i * 1000 + int(rng.randint(0, 500))
        b.write(t, ts, bytes([i % 256]) * payload)
    b.close()
    return path


def det_logic(msg):
    return ("/det" + msg.topic, msg.data[:4])


def det_batch_logic(msgs):
    return [("/det" + m.topic, m.timestamp, m.data[:4]) for m in msgs]


@pytest.fixture
def bag_path(tmp_path):
    return _make_bag(str(tmp_path / "drive.bag"))


# -- queued bus semantics ---------------------------------------------------


def test_queued_fifo_order_under_backpressure():
    """A slow queued subscriber with a tiny bounded FIFO still sees every
    message of every topic in publish order — backpressure blocks the
    publisher instead of dropping or reordering."""
    bus = MessageBus()
    seen = []

    def slow(msg):
        time.sleep(0.0003)
        seen.append((msg.topic, msg.timestamp))

    bus.subscribe(None, slow, mode="queued", maxsize=2)
    expect = []
    for i in range(120):
        topic = f"/t{i % 3}"
        bus.advertise(topic).publish(i, b"x")
        expect.append((topic, i))
    bus.drain()
    assert seen == expect                       # global publish order
    for t in ("/t0", "/t1", "/t2"):             # per-topic FIFO
        per = [ts for tt, ts in seen if tt == t]
        assert per == sorted(per)
    bus.close()


def test_queued_backpressure_bounds_queue():
    """The publisher measurably blocks once the lane is full (bounded
    memory), and the in-flight backlog never exceeds maxsize."""
    bus = MessageBus()
    release = threading.Event()
    got = []

    def gated(msg):
        release.wait(5.0)
        got.append(msg.timestamp)

    bus.subscribe("/t", gated, mode="queued", maxsize=2)
    pub = bus.advertise("/t")
    # worker holds msg 0 inside the gated callback; 1 and 2 fill the FIFO
    for i in range(3):
        pub.publish(i, b"")
    blocked = threading.Event()

    def producer():
        blocked.set()
        pub.publish(99, b"")                    # must block: lane full

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    blocked.wait(5.0)
    time.sleep(0.05)
    assert t.is_alive()                         # still stuck in put()
    release.set()
    t.join(5.0)
    bus.drain()
    assert got == [0, 1, 2, 99]
    bus.close()


def test_drain_flushes_before_record_stop():
    """Every message published before RosRecord.stop() is in the bag when
    stop() returns, even with a queued (lagging) recorder lane."""
    bus = MessageBus()
    out = Bag.open_write(backend="memory")
    rec = RosRecord(bus, out, mode="queued", queue_maxsize=4)
    rec.start()
    pub = bus.advertise("/a")
    for i in range(200):
        pub.publish(i, bytes([i % 256]))
    rec.stop()                                  # flushes the recorder lane
    assert rec.messages_recorded == 200
    out.close()
    got = Bag.open_read(backend="memory", image=out.chunked_file.image())
    assert got.num_messages == 200
    assert [m.timestamp for m in got.read_messages()] == list(range(200))
    bus.close()


def test_adaptive_lane_deepens_for_slow_sink():
    """With ``maxsize=None`` a lane observes its producer outrunning a
    slow sink and converges to a deeper FIFO — bounded by the memory cap —
    while still delivering every message in order (ROADMAP follow-up)."""
    from repro.core.playback import _Lane
    bus = MessageBus()
    seen = []

    def slow(msg):
        time.sleep(0.002)
        seen.append(msg.timestamp)

    bus.subscribe("/t", slow, mode="queued", maxsize=None)
    lane = next(iter(bus._lanes.values()))
    assert lane.depth == _Lane.ADAPTIVE_START
    pub = bus.advertise("/t")
    for i in range(100):
        pub.publish(i, b"x")
    grown_depth = lane.depth
    assert _Lane.ADAPTIVE_START < grown_depth <= _Lane.ADAPTIVE_MAX
    assert lane.grown > 0
    bus.drain()
    assert seen == list(range(100))             # order never moved
    bus.close()


def test_fixed_lane_depth_never_adapts():
    """An explicit ``maxsize`` stays put under the same pressure — the
    adaptive behaviour is opt-in via None."""
    bus = MessageBus()

    def slow(msg):
        time.sleep(0.001)

    bus.subscribe("/t", slow, mode="queued", maxsize=4)
    pub = bus.advertise("/t")
    for i in range(60):
        pub.publish(i, b"x")
    lane = next(iter(bus._lanes.values()))
    assert lane.depth == 4 and lane.grown == 0
    bus.drain()
    bus.close()


def test_scenario_default_queue_depth_is_adaptive(bag_path):
    """Scenario.queue_depth=None (the default) runs staged partitions on
    adaptive lanes and still produces bit-identical results to a fixed
    depth."""
    def scenarios(depth):
        return [Scenario("s", bag_path, det_logic, pipeline=True,
                         queue_depth=depth)]

    fixed = ScenarioSuite(scenarios(8), num_workers=2).run(timeout=60)
    adaptive = ScenarioSuite(scenarios(None), num_workers=2).run(timeout=60)
    assert (fixed["s"].report.output_image
            == adaptive["s"].report.output_image)
    assert ({t: m.checksum for t, m in fixed["s"].metrics.items()}
            == {t: m.checksum for t, m in adaptive["s"].metrics.items()})
    with pytest.raises(ValueError):
        Scenario("bad", bag_path, det_logic, queue_depth=0)


def test_queued_batch_subscription_gets_whole_batches():
    bus = MessageBus()
    batches = []
    bus.subscribe_batch("/a", batches.append, mode="queued", maxsize=2)
    msgs = [Message("/a", i, b"") for i in range(10)]
    bus.publish_batch(msgs[:6])
    bus.publish_batch(msgs[6:])
    bus.drain()
    assert [len(b) for b in batches] == [6, 4]
    assert [m.timestamp for b in batches for m in b] == list(range(10))
    bus.close()


def test_shared_group_lane_preserves_cross_topic_order():
    """Subscriptions sharing a group= share one FIFO + worker: combined
    delivery order across topics is exactly the publish order (what keeps
    the fault-profile RNG deterministic in staged replay)."""
    bus = MessageBus()
    order = []

    def cb_a(m):
        order.append(("a", m.timestamp))

    def cb_b(m):
        order.append(("b", m.timestamp))

    bus.subscribe("/a", cb_a, mode="queued", group="logic")
    bus.subscribe("/b", cb_b, mode="queued", group="logic")
    for i in range(50):
        bus.advertise("/a" if i % 2 == 0 else "/b").publish(i, b"")
    bus.drain()
    assert order == [("a" if i % 2 == 0 else "b", i) for i in range(50)]
    bus.close()


def test_queued_callback_error_surfaces_at_drain():
    bus = MessageBus()

    def boom(msg):
        raise RuntimeError("subscriber exploded")

    bus.subscribe("/t", boom, mode="queued")
    bus.advertise("/t").publish(0, b"")
    with pytest.raises(RuntimeError, match="subscriber exploded"):
        bus.drain()
    bus.close()                                 # close never raises


def test_double_subscribe_is_an_error():
    """Registering the same callback twice on the same topic raises —
    unsubscribe removes exactly one entry, so a silent duplicate would
    leave a phantom subscription behind (the seed-era bug)."""
    bus = MessageBus()
    hits = []
    bus.subscribe("/t", hits.append)
    with pytest.raises(ValueError, match="already subscribed"):
        bus.subscribe("/t", hits.append)
    bus.subscribe("/u", hits.append)            # other topics still fine
    bus.subscribe(None, hits.append)            # the -a registry too
    with pytest.raises(ValueError, match="already subscribed"):
        bus.subscribe(None, hits.append)
    bus.subscribe_batch("/t", hits.append)
    with pytest.raises(ValueError, match="already subscribed"):
        bus.subscribe_batch("/t", hits.append)
    # after unsubscribe, the registrations are truly gone
    bus.unsubscribe("/t", hits.append)
    bus.unsubscribe(None, hits.append)
    bus.advertise("/t").publish(1, b"x")
    assert hits == []
    assert bus.published == 1


def test_unsubscribe_unknown_callback_raises():
    bus = MessageBus()
    with pytest.raises(ValueError, match="not subscribed"):
        bus.unsubscribe("/t", lambda m: None)


# -- overlap beats synchronous ---------------------------------------------


def test_slow_subscriber_overlap_beats_sync_wall_clock(tmp_path):
    """The point of the staged pipeline: with a deliberately slow
    subscriber next to a working logic stage, queued delivery overlaps
    the two and beats the synchronous shape on wall clock, with identical
    delivery counts."""
    p = _make_bag(str(tmp_path / "slow.bag"), n=900)

    def run(mode):
        bus = MessageBus()
        counts = {"logic": 0, "slow": 0}

        def logic(msgs):
            time.sleep(0.002)
            counts["logic"] += len(msgs)

        def slow_monitor(msgs):
            time.sleep(0.004)                   # the laggard
            counts["slow"] += len(msgs)

        for t in TOPICS:
            bus.subscribe_batch(t, logic, mode=mode, group="logic")
        bus.subscribe_batch(None, slow_monitor, mode=mode)
        t0 = time.perf_counter()
        n = RosPlay(Bag.open_read(p), bus).run_batched(
            60, prefetch=2 if mode == "queued" else 0)
        bus.drain()
        wall = time.perf_counter() - t0
        bus.close()
        return n, counts, wall

    # interleaved best-of-2: scheduler jitter on a loaded CI box can
    # swamp a single run, so compare the fastest of each mode and demand
    # a real margin (theoretical floor here is ~0.6) without flaking
    n_sync, c_sync, wall_sync = run("sync")
    n_q, c_q, wall_q = run("queued")
    wall_sync = min(wall_sync, run("sync")[2])
    wall_q = min(wall_q, run("queued")[2])
    assert n_sync == n_q == 900
    assert c_sync == c_q
    assert c_q["slow"] == 900
    assert wall_q < wall_sync * 0.85, (wall_q, wall_sync)


# -- sync vs staged bit-parity ---------------------------------------------


def _checksums(verdicts):
    return {name: {t: m.checksum for t, m in v.metrics.items()}
            for name, v in verdicts.items()}


@pytest.mark.parametrize("backend", ["thread", "process"])
def test_verdicts_bit_identical_sync_vs_staged(bag_path, tmp_path, backend):
    """Acceptance: suite verdicts and metric checksums are bit-identical
    between synchronous and staged replay — the pipeline is an overlap
    optimisation, not a semantic change.  Includes a drop-rate scenario
    (RNG draw order) and a golden comparison."""
    golden = str(tmp_path / "golden.bag")
    base = ScenarioSuite([Scenario("g", bag_path, det_logic,
                                   pipeline=False)]).run()["g"].report
    gbag = base.open_output_bag()
    out = Bag.open_write(golden)
    for m in gbag.read_messages():
        out.write_message(m)
    out.close()

    def scenarios(staged):
        return [
            Scenario("plain", bag_path, det_logic, pipeline=staged,
                     golden_bag_path=golden),
            Scenario("batched", bag_path, det_batch_logic, batch_size=32,
                     pipeline=staged),
            Scenario("droppy", bag_path, det_logic, drop_rate=0.3, seed=7,
                     pipeline=staged),
            Scenario("batch-drop", bag_path, det_batch_logic, batch_size=25,
                     drop_rate=0.2, seed=3, pipeline=staged),
        ]

    v_sync = ScenarioSuite(scenarios(False), num_workers=3,
                           backend=backend).run(timeout=180)
    v_staged = ScenarioSuite(scenarios(True), num_workers=3,
                             backend=backend).run(timeout=180)
    assert {n: v.status for n, v in v_sync.items()} \
        == {n: v.status for n, v in v_staged.items()}
    assert all(v.passed for v in v_staged.values())
    assert _checksums(v_sync) == _checksums(v_staged)
    for name in v_sync:
        rs, rq = v_sync[name].report, v_staged[name].report
        assert (rs.messages_in, rs.messages_out, rs.messages_dropped) \
            == (rq.messages_in, rq.messages_out, rq.messages_dropped)
        assert rs.output_image == rq.output_image       # byte-identical bag


def test_metrics_engines_bit_identical(bag_path):
    """The sink-stage digest engines (numpy / jax / fused Pallas consume
    step) can never move a checksum."""
    results = {}
    for engine in ("numpy", "jax", "fused"):
        v = ScenarioSuite([
            Scenario("s", bag_path, det_batch_logic, batch_size=32,
                     metrics_engine=engine)]).run()
        results[engine] = _checksums(v)["s"]
        assert results[engine]                  # non-empty metrics
    assert results["numpy"] == results["jax"] == results["fused"]


def test_staged_partition_logic_error_fails_task(bag_path):
    """An exploding user logic inside a queued lane worker must fail the
    task (surface at the drain barrier), not silently truncate output."""
    from repro.core.scheduler import WorkerError

    with pytest.raises(WorkerError):
        ScenarioSuite([Scenario(
            "boom", bag_path,
            f"{__name__}:_exploding_logic", pipeline=True)],
            num_workers=2,
            scheduler_kwargs={"max_attempts": 2}).run(timeout=60)


def _exploding_logic(msg):
    raise RuntimeError("user logic exploded")


def test_pipeline_auto_resolution(bag_path):
    """pipeline=None stages exactly the latency-modeling scenarios (where
    the logic stage yields and overlap pays); free-running logic keeps the
    synchronous hot loop; explicit settings always win."""
    assert not Scenario("a", bag_path, det_logic).staged
    assert Scenario("b", bag_path, det_logic,
                    latency_model_s=0.001).staged
    assert Scenario("c", bag_path, det_logic, pipeline=True).staged
    assert not Scenario("d", bag_path, det_logic, pipeline=False,
                        latency_model_s=0.001).staged


def test_record_stop_is_exception_safe():
    """A deferred lane write error surfaces once at stop(); a retried
    stop() is a clean no-op instead of masking the real error with
    'not subscribed'."""
    bus = MessageBus()
    bag = Bag.open_write(backend="memory")
    bag.close()                                 # writes will now raise
    rec = RosRecord(bus, bag, mode="queued")
    rec.start()
    bus.advertise("/t").publish(0, b"x")
    with pytest.raises(Exception):
        rec.stop()                              # deferred write error
    rec.stop()                                  # bookkeeping already clean
    bus.close()


def test_bus_side_exclusion_skips_enqueue():
    """exclude_topics filters at dispatch: excluded traffic is never
    delivered — and for queued subscriptions never enqueued, so it cannot
    consume the lane's backpressure budget."""
    bus = MessageBus()
    seen, seen_batches = [], []
    bus.subscribe(None, seen.append, mode="queued", maxsize=1,
                  exclude_topics=["/in"])
    bus.subscribe_batch(None, seen_batches.append, mode="queued", maxsize=1,
                        exclude_topics=["/in"])
    # a maxsize-1 lane would deadlock-ish stall this loop if excluded
    # messages were enqueued; they aren't, so it flies through
    pub = bus.advertise("/in")
    for i in range(100):
        pub.publish(i, b"")
    bus.publish_batch([Message("/in", 100, b""), Message("/out", 101, b"")])
    bus.drain()
    assert [m.timestamp for m in seen] == [101]
    assert [[m.timestamp for m in b] for b in seen_batches] == [[101]]
    bus.close()


# -- spill-aware aggregate dispatch ----------------------------------------


def test_aggregate_args_ride_the_spill(tmp_path):
    """On the process backend, partition images bound for the aggregate
    task are parked in the backend spill dir and shipped as paths — the
    workers merge via streaming disk readers, and the verdict still
    carries the complete merged output."""
    p = _make_bag(str(tmp_path / "big.bag"), n=400, payload=512)
    backend = ProcessBackend(spill_bytes=4096)
    verdicts = ScenarioSuite(
        [Scenario("spilled", p, f"{__name__}:_full_logic",
                  num_partitions=4)],
        num_workers=2, backend=backend).run(timeout=120)
    assert backend.arg_spills >= 1
    rep = verdicts["spilled"].report
    assert rep.messages_out == 400
    assert rep.open_output_bag().num_messages == 400
    assert verdicts["spilled"].passed


def _full_logic(msg):
    return ("/det" + msg.topic, msg.data)       # keep the full payload


def test_aggregate_small_args_skip_the_spill(bag_path):
    backend = ProcessBackend(spill_bytes=1 << 20)   # images are ~KB here
    ScenarioSuite([Scenario("small", bag_path, f"{__name__}:det_logic")],
                  num_workers=2, backend=backend).run(timeout=120)
    assert backend.arg_spills == 0


# -- verdict persistence ----------------------------------------------------


def test_verdict_log_and_manifest(bag_path, tmp_path):
    log = str(tmp_path / "verdicts.jsonl")
    scenarios = [
        Scenario("a", bag_path, det_logic),
        Scenario("b", bag_path, det_batch_logic, batch_size=32),
    ]
    ScenarioSuite(scenarios, num_workers=2).run(verdict_log=log)
    lines = [json.loads(ln) for ln in open(log)]
    assert {ln["scenario"] for ln in lines} == {"a", "b"}
    for ln in lines:
        assert ln["status"] == "PASS" and ln["passed"]
        assert ln["messages_in"] == 600
        assert ln["checksums"]                  # per-topic digests logged
        assert ln["wall_time_s"] > 0
        assert ln["backend"] == "thread"

    manifest = json.load(open(log + ".manifest.json"))
    assert manifest["passed"] is True
    assert set(manifest["scenarios"]) == {"a", "b"}
    assert manifest["scenarios"]["a"]["golden"] is None
    assert manifest["verdict_log"].endswith("verdicts.jsonl")

    # append-only history: a second run doubles the log, manifest is
    # rewritten as the current snapshot
    ScenarioSuite(scenarios, num_workers=2).run(verdict_log=log)
    assert len(list(open(log))) == 4
    manifest2 = json.load(open(log + ".manifest.json"))
    assert set(manifest2["scenarios"]) == {"a", "b"}


def test_verdict_log_records_failures(bag_path, tmp_path):
    """A FAIL lands in the log and flips the manifest — the CI-native
    signal."""
    golden = str(tmp_path / "golden.bag")
    rep = ScenarioSuite([Scenario("g", bag_path, det_logic)],
                        num_workers=2).run()["g"].report
    gbag = rep.open_output_bag()
    out = Bag.open_write(golden)
    for m in gbag.read_messages():
        out.write_message(m)
    out.close()

    log = str(tmp_path / "verdicts.jsonl")
    verdicts = ScenarioSuite([
        Scenario("regressed", bag_path, f"{__name__}:_truncating_logic",
                 golden_bag_path=golden)],
        num_workers=2).run(verdict_log=log)
    assert not verdicts["regressed"].passed
    (line,) = [json.loads(ln) for ln in open(log)]
    assert line["status"] == "FAIL" and line["diffs"]
    manifest = json.load(open(log + ".manifest.json"))
    assert manifest["passed"] is False
    assert manifest["scenarios"]["regressed"]["golden"] == golden


def _truncating_logic(msg):
    return ("/det" + msg.topic, msg.data[:2])   # wrong payload vs golden


# -- prefetch ---------------------------------------------------------------


def test_prefetched_batches_match_unprefetched(bag_path):
    from repro.data.pipeline import iter_message_batches
    from repro.core import iter_time_ordered

    bag = Bag.open_read(bag_path)
    plain = [[m.timestamp for m in b]
             for b in iter_message_batches(iter_time_ordered(bag), 64)]
    bag2 = Bag.open_read(bag_path)
    pre = [[m.timestamp for m in b]
           for b in iter_message_batches(iter_time_ordered(bag2), 64,
                                         prefetch=2)]
    assert plain == pre
    bag.close()
    bag2.close()


def test_prefetch_iterator():
    from repro.data.pipeline import PrefetchIterator

    def slow_gen():
        for i in range(5):
            time.sleep(0.01)
            yield i
    assert list(PrefetchIterator(slow_gen())) == list(range(5))


def test_prefetch_propagates_errors():
    from repro.data.pipeline import PrefetchIterator

    def bad_gen():
        yield 1
        raise RuntimeError("boom")
    it = PrefetchIterator(bad_gen())
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="boom"):
        list(it)


def test_prefetch_close_stops_abandoned_reader():
    """A consumer that bails early must be able to stop the reader thread
    even while it is blocked on the full queue (no leaked thread pinning
    the source)."""
    from repro.data.pipeline import PrefetchIterator

    it = PrefetchIterator(iter(range(100000)), depth=1)
    assert next(it) == 0                        # reader is now wedged full
    it.close()
    assert not it._thread.is_alive()
    # and a normally-exhausted iterator still terminates cleanly
    it2 = PrefetchIterator(iter(range(3)), depth=1)
    assert list(it2) == [0, 1, 2]
    it2.close()


def test_prefetch_worker_exception_propagates_then_stops():
    """A source iterator that raises mid-stream surfaces the error exactly
    once in __next__; the worker thread exits and is joinable."""
    from repro.data.pipeline import PrefetchIterator

    def src():
        yield 1
        yield 2
        raise ValueError("source died")

    it = PrefetchIterator(src(), depth=4)
    got = []
    with pytest.raises(ValueError, match="source died"):
        for x in it:
            got.append(x)
    assert got == [1, 2]
    it._thread.join(timeout=2.0)
    assert not it._thread.is_alive()
    # the error surfaced once; the stream is simply over afterwards
    with pytest.raises(StopIteration):
        next(it)
    it.close()                                  # idempotent after the fact


def test_prefetch_close_after_worker_exception_does_not_hang():
    """Regression: close() while the dead worker's done-sentinel (or a
    buffered item) still clogs the full queue must return promptly with the
    thread joined — not block forever on a queue nobody will drain."""
    from repro.data.pipeline import PrefetchIterator

    def src():
        yield b"a"          # fills the depth-1 queue
        yield b"b"          # worker blocks putting this one
        raise ValueError("never reached until the queue drains")

    it = PrefetchIterator(src(), depth=1)
    assert next(it) == b"a"
    t0 = time.monotonic()
    it.close()
    assert time.monotonic() - t0 < 3.0
    assert not it._thread.is_alive()
    with pytest.raises(StopIteration):          # closed stream stays closed
        next(it)


def test_prefetch_blocked_next_unblocks_on_close():
    """A consumer parked in __next__ on an empty queue (source stalled)
    must observe close() and end the stream instead of hanging."""
    from repro.data.pipeline import PrefetchIterator

    release = threading.Event()

    def src():
        yield 0
        release.wait(10.0)                      # stalled source
        yield 1

    it = PrefetchIterator(src(), depth=1)
    assert next(it) == 0
    outcome = []

    def consume():
        try:
            next(it)
            outcome.append("item")
        except StopIteration:
            outcome.append("stop")

    consumer = threading.Thread(target=consume)
    consumer.start()
    time.sleep(0.15)                            # let it park in the poll
    closer = threading.Thread(target=it.close)
    closer.start()
    consumer.join(timeout=2.0)
    assert outcome == ["stop"]
    release.set()                               # un-stall so close() joins
    closer.join(timeout=2.0)
    assert not closer.is_alive()
    assert not it._thread.is_alive()


def test_rosplay_prefetch_survives_subscriber_error(bag_path):
    """A synchronous subscriber raising mid-replay must not leak the
    prefetch reader: run() propagates the error and stops the reader."""
    bus = MessageBus()
    calls = []

    def boom(msg):
        calls.append(msg)
        if len(calls) >= 5:
            raise RuntimeError("mid-replay failure")

    bus.subscribe(None, boom)
    play = RosPlay(Bag.open_read(bag_path), bus)
    with pytest.raises(RuntimeError, match="mid-replay failure"):
        play.run(prefetch=8)
    assert len(calls) == 5


def test_rosplay_prefetch_is_order_identical(bag_path):
    def stamps(prefetch):
        bus = MessageBus()
        seen = []
        bus.subscribe(None, lambda m: seen.append(m.timestamp))
        RosPlay(Bag.open_read(bag_path), bus).run(prefetch=prefetch)
        return seen

    assert stamps(0) == stamps(64)
