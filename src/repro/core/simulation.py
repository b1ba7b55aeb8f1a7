"""The scenario engine: distributed simulation over a suite of scenarios
(paper Fig 3 + Fig 5 workflow, generalized from "replay one bag" to "run a
test matrix over a drive fleet").

    Scenario catalog --ScenarioSuite--> Scheduler/ExecutorBackend
        --RosPlay--> MessageBus --User Logic--> RosRecord --> Bag
        --Aggregator--> merged Bag + metrics --> Verdict

A :class:`Scenario` describes one functional/performance test: one bag
(``bag_path``) or a sharded fleet of bags (``bag_paths``), a topic filter,
a time window, a latency/fault profile, a user-logic ref and an optional
golden bag.  A :class:`ScenarioSuite` fans every partition of every shard
of every scenario through ONE scheduler (thread or process backend), then
hands each scenario's partition outputs to the aggregation layer
(:mod:`repro.core.aggregation`): shard outputs are k-way merged into one
timestamp-ordered bag, per-topic metrics are computed, golden bags are
compared, and ``run`` returns per-scenario :class:`Verdict`\\ s — the
paper's "massive test suites over a shared cluster", scored.

Per the paper: "Each Spark worker first reads the Rosbag data into memory
and then launches a ROS node to process the incoming data."  Here each task:

1. reads its chunk-range partition from the source bag (applying the
   scenario's topic filter and time window),
2. copies it into a ``MemoryChunkedFile``-backed bag (the ROSBag cache —
   this is the I/O optimisation §4.1 measures),
3. replays it through the user logic attached to the bus — per message, or
   in timestamp-ordered micro-batches when ``Scenario.batch_size`` is set
   (``RosPlay.run_batched`` -> ``MessageBus.publish_batch``), so the logic
   can be a jitted array step over assembled batches
   (:func:`repro.data.pipeline.assemble_message_batch` +
   :func:`repro.kernels.sensor_decode.sensor_decode`),
4. records outputs into a memory bag and ships its image plus KB-sized
   partial per-topic metrics (a streaming :class:`MetricsTap` on the sink
   side — fork-safe numpy digests on process workers, the fused Pallas
   consume step for batched in-process scenarios) as the task result;
   per-scenario aggregation then runs as its own scheduled task
   (lineage stage ``"aggregate"``), overlapping remaining replay work.
   Latency-modeling scenarios replay as a staged read → logic → record
   pipeline over queued bus lanes (``Scenario.pipeline``), overlapping
   disk I/O, compute and bag serialization inside each task.

``user_logic`` contracts:
  per-message : ``Message -> Optional[(topic, bytes)]`` (output inherits the
                input timestamp — the seed contract),
  batched     : ``list[Message] -> Optional[iterable[(topic, ts, bytes)]]``.
Either may be given as a ``"module:attr"`` string ref, resolved inside the
worker — required for the process backend, where the callable must cross a
pickle boundary.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import random
import sys
import threading
import time
import zlib
from dataclasses import dataclass, field, fields as dataclass_fields
from typing import Callable, Optional, Sequence, Union

from repro import chaos
from repro.obs import metrics as obs_metrics
from repro.obs import trace as otrace
from repro.shm import SegmentHandle, read_segment, shm_available

from .aggregation import Aggregator, MetricsTap, TopicMetrics, Verdict
from .bag import Bag, Message, partition_bag
from .binpipe import BinaryPartition, encode
from .executors import ExecutorBackend
from .playback import (MESSAGE_PREFETCH, TRACE_CHUNK, MessageBus, RosPlay,
                       RosRecord)
from .scheduler import Scheduler

UserLogic = Callable[[Message], Optional[tuple[str, bytes]]]
BatchUserLogic = Callable[[Sequence[Message]],
                          Optional[Sequence[tuple[str, int, bytes]]]]
LogicRef = Union[UserLogic, BatchUserLogic, str]


def resolve_logic_ref(ref: LogicRef) -> Callable:
    """Resolve a ``"package.module:attr"`` string ref to the callable it
    names; callables pass through.  String refs are what a process-backend
    scenario ships across the pickle boundary.

    ``"perception://<model>"`` refs resolve to the stock jitted
    decode→forward batched logic (:mod:`repro.perception`), cached per
    process so every partition naming the same model shares one compiled
    step and one deterministic param set.  Perception scenarios must set
    ``batch_size`` (the step consumes assembled batches) and run on
    in-process backends (see :class:`ScenarioSuite`).
    """
    if callable(ref):
        return ref
    if str(ref).startswith("perception://"):
        from repro.perception import get_step
        return get_step(str(ref))
    mod_name, _, attr = str(ref).partition(":")
    if not attr:
        raise ValueError(f"logic ref {ref!r} is not 'module:attr'")
    fn = getattr(importlib.import_module(mod_name), attr)
    if not callable(fn):
        raise TypeError(f"logic ref {ref!r} resolved to non-callable {fn!r}")
    return fn


def _logic_fingerprint(ref: LogicRef) -> str:
    """Canonical content-addressable identity of a user-logic ref.

    String refs (``"module:attr"`` / ``"perception://<model>"``) are their
    own identity.  A module-level callable is accepted iff it re-resolves
    to itself through its ``module:qualname`` — the same contract the
    process backend already imposes — and fingerprints as that ref.
    Lambdas, closures and bound methods have no stable identity across
    runs, so they raise: a scenario carrying one is simply *uncacheable*
    (the suite replays it every time rather than risking a stale hit).
    """
    if isinstance(ref, str):
        return ref
    mod = getattr(ref, "__module__", None)
    qualname = getattr(ref, "__qualname__", None)
    if mod and qualname and "<" not in qualname:
        try:
            obj: object = importlib.import_module(mod)
            for part in qualname.split("."):
                obj = getattr(obj, part)
        except (ImportError, AttributeError):
            obj = None
        if obj is ref:
            return f"{mod}:{qualname}"
    raise ValueError(
        f"user_logic {ref!r} has no stable content identity (lambda, "
        "closure or non-importable callable); use a 'module:attr' ref to "
        "make the scenario cacheable")


#: Scenario fields that name *where* content lives rather than *what* runs;
#: the result-cache key digests their content separately (bag/golden
#: digests), so renaming a scenario or moving a bag never invalidates.
_FINGERPRINT_EXCLUDE = ("name", "bag_path", "bag_paths", "golden_bag_path")


@dataclass(frozen=True)
class Scenario:
    """One entry of the test matrix.

    The bag source is either ``bag_path`` (one recorded drive) or
    ``bag_paths`` (a sharded fleet — one bag per vehicle/segment); exactly
    one must be given.  Every shard is partitioned, replayed and recorded
    independently; the aggregation layer merges the shard outputs back
    into one timestamp-ordered result bag.  ``num_partitions`` is
    *per shard*.

    ``batch_size=None`` replays per message (seed behaviour); an integer
    switches to batched replay and the batched user-logic contract.
    ``drop_rate`` is the fault profile: that fraction of input messages is
    dropped (deterministically, per ``seed``) before reaching user logic —
    simulated sensor dropouts.  ``latency_model_s`` sleeps once per user
    logic invocation (per message, or per batch — batching amortizes it,
    like a real accelerator-offloaded model step).

    ``golden_bag_path`` names a recorded expected-output bag; the
    aggregator diffs the merged output against it (exact or
    tolerance-based, see :class:`repro.core.aggregation.Aggregator`) and
    the scenario's verdict fails on any mismatch.

    ``pipeline`` selects the partition replay shape: ``True`` is the
    staged read → logic → record pipeline over queue-backed bus
    subscriptions (disk I/O, user logic and bag serialization overlap),
    ``False`` the synchronous seed shape, and ``None`` (default) resolves
    automatically — staged when the scenario models per-invocation
    compute latency (``latency_model_s > 0``, the regime where the logic
    stage yields and overlap wins), synchronous for free-running logic
    where queue handoffs would only tax the hot loop.  Outputs, metrics
    and verdicts are bit-identical either way, so the switch is purely a
    performance choice.  ``queue_depth`` bounds each pipeline stage's
    FIFO (the backpressure window); ``None`` (default) is **adaptive** —
    lanes start shallow and deepen themselves while the producer outruns
    the sink, bounded by a memory cap (see
    :class:`repro.core.playback.MessageBus`).  ``metrics_engine`` picks the
    sink-stage digest reduction
    (:class:`repro.core.aggregation.MetricsTap`): ``"auto"`` resolves to
    the fused Pallas consume step for batched in-process scenarios and the
    fork-safe numpy engine otherwise (process workers never init jax).
    ``ts_sketch`` bounds the sink's per-topic timestamp state to a KMV
    sample of that many values (see
    :class:`repro.core.aggregation.TopicMetrics`): counts, bounds and
    checksums — everything golden verdicts read — stay exact; gap
    percentiles become estimates.  ``None`` (default) keeps exact
    multisets.

    ``exports``/``imports`` wire scenarios together through the
    distributed message pool (:mod:`repro.net`): a scenario's user-logic
    outputs on its ``exports`` topics are routed — in-process or over
    cross-process transports, the suite decides — to every scenario that
    lists those topics in ``imports``.  An importing scenario replays the
    merged, timestamp-ordered import stream through its user logic as one
    extra partition (inputs, like bag traffic: excluded from its own
    recording), scheduled once all its providers finish.  The routing
    graph must be a DAG and each topic may have exactly one exporter; a
    topic cannot appear in both tuples of one scenario.  Outputs are
    bit-identical whichever transport shape carries the stream.
    """
    name: str
    bag_path: Optional[str] = None
    user_logic: LogicRef = None
    topics: Optional[tuple[str, ...]] = None
    start: Optional[int] = None          # time window, ns (inclusive)
    end: Optional[int] = None            # time window, ns (exclusive)
    latency_model_s: float = 0.0
    drop_rate: float = 0.0
    seed: int = 0
    batch_size: Optional[int] = None
    num_partitions: Optional[int] = None
    use_memory_cache: bool = True
    bag_paths: Optional[tuple[str, ...]] = None   # fleet shards
    golden_bag_path: Optional[str] = None
    pipeline: Optional[bool] = None      # None = auto (see docstring)
    queue_depth: Optional[int] = None    # None = adaptive lanes
    metrics_engine: str = "auto"
    ts_sketch: Optional[int] = None      # None = exact timestamp multisets
    exports: Optional[tuple[str, ...]] = None     # topics fed to importers
    imports: Optional[tuple[str, ...]] = None     # topics fed by exporters

    def __post_init__(self):
        if self.user_logic is None:
            raise ValueError(f"scenario {self.name!r} has no user_logic")
        if self.metrics_engine not in ("auto", "numpy", "jax", "fused"):
            raise ValueError(f"scenario {self.name!r}: unknown "
                             f"metrics_engine {self.metrics_engine!r}")
        if self.ts_sketch is not None and self.ts_sketch < 1:
            raise ValueError(f"scenario {self.name!r}: ts_sketch >= 1 "
                             "(or None for exact timestamp multisets)")
        if (isinstance(self.user_logic, str)
                and self.user_logic.startswith("perception://")
                and self.batch_size is None):
            raise ValueError(
                f"scenario {self.name!r}: perception:// logic is batched — "
                "set batch_size")
        if self.queue_depth is not None and self.queue_depth < 1:
            raise ValueError(f"scenario {self.name!r}: queue_depth >= 1 "
                             "(or None for adaptive)")
        if (self.bag_path is None) == (self.bag_paths is None):
            raise ValueError(f"scenario {self.name!r}: give exactly one of "
                             "bag_path / bag_paths")
        if self.bag_paths is not None and not isinstance(self.bag_paths,
                                                         tuple):
            object.__setattr__(self, "bag_paths", tuple(self.bag_paths))
        for fld in ("exports", "imports"):
            v = getattr(self, fld)
            if v is not None and not isinstance(v, tuple):
                object.__setattr__(self, fld, tuple(v))
        if self.exports and self.imports:
            both = set(self.exports) & set(self.imports)
            if both:
                raise ValueError(
                    f"scenario {self.name!r}: topics {sorted(both)} are "
                    "both imported and exported — relaying a topic through "
                    "a scenario is ambiguous; transform it onto a new topic")

    @property
    def shard_paths(self) -> tuple[str, ...]:
        """The fleet as a tuple of bag paths (length 1 for ``bag_path``)."""
        return ((self.bag_path,) if self.bag_path is not None
                else self.bag_paths)

    def fingerprint(self) -> str:
        """Canonical SHA-256 over every replay-relevant parameter — the
        scenario term of the result-cache key (:mod:`repro.cache`).

        Covers the topic filter, time window, latency/drop profiles and
        seed, batch/queue/pipeline parameters, metric engine and sketch
        settings, the exports/imports wiring and the user-logic ref —
        every dataclass field except the scenario *name* and the bag /
        golden *paths* (their content is digested separately, so a
        rename or relocation with identical bytes still hits).  Any
        parameter change produces a new fingerprint and forces a clean
        re-replay.  Raises ``ValueError`` when the user logic has no
        stable content identity (see :func:`_logic_fingerprint`) — such
        scenarios are uncacheable, never wrongly cached.
        """
        spec = {}
        for f in dataclass_fields(self):
            if f.name in _FINGERPRINT_EXCLUDE:
                continue
            value = getattr(self, f.name)
            if f.name == "user_logic":
                value = _logic_fingerprint(value)
            spec[f.name] = value
        return hashlib.sha256(
            json.dumps(spec, sort_keys=True).encode()).hexdigest()

    @property
    def staged(self) -> bool:
        """The resolved replay shape: explicit ``pipeline`` wins; auto
        (``None``) stages exactly the latency-modeling scenarios, where
        the logic stage sleeps/offloads and overlap pays — free-running
        logic keeps the zero-handoff synchronous hot loop."""
        if self.pipeline is not None:
            return self.pipeline
        return self.latency_model_s > 0


@dataclass
class SimulationReport:
    """Per-scenario replay outcome, post-aggregation.

    ``output_image`` is the merged, timestamp-ordered output bag (all
    shards, all partitions — one image), and ``metrics`` the per-topic
    :class:`TopicMetrics` the aggregator computed over it.  The seed-era
    per-partition image list (``partition_images`` / the deprecated
    ``output_images`` accessor) is gone: the driver holds exactly one
    merged image per scenario.
    """
    messages_in: int
    messages_out: int
    wall_time_s: float
    partitions: int
    scheduler_stats: dict
    scenario: str = ""
    backend: str = ""
    batch_size: Optional[int] = None
    messages_dropped: int = 0
    shards: int = 1
    output_image: Optional[bytes] = None     # merged output bag image
    metrics: dict[str, TopicMetrics] = field(default_factory=dict)

    @property
    def throughput_msgs_s(self) -> float:
        return self.messages_in / self.wall_time_s if self.wall_time_s else 0.0

    def open_output_bag(self) -> Bag:
        """The merged output as a readable memory bag."""
        if self.output_image is None:
            raise ValueError("report has no merged output image")
        return Bag.open_read(backend="memory", image=self.output_image)


def _run_scenario_partition(scenario: Scenario, source: "str | bytes",
                            chunk_range: Optional[tuple[int, int]],
                            metrics_engine: str = "numpy",
                            export_to: Optional[tuple[str, int, str]] = None,
                            rng_tag: Optional[str] = None,
                            collect_exports: bool = False,
                            ) -> tuple[int, int, int, bytes, dict,
                                       Optional[list]]:
    """One worker task: play one shard partition through the user logic.

    ``source`` is a disk bag path or a memory-bag image (bytes — either
    shape may arrive for an *import partition*: the driver ships the
    merged import stream inline or as a spill path, see
    :class:`ScenarioSuite`).  ``chunk_range=None`` marks an import
    partition: the whole source replays and the scenario's topic/time
    selection does **not** re-filter it (the driver already filtered by
    ``Scenario.imports``; the provider's selection shaped the stream).
    ``rng_tag`` overrides the shard-path term of the drop-RNG seed so an
    import partition draws identically whether its stream arrived as
    bytes or as a spill path.

    Export routing: when ``scenario.exports`` is consumed by the suite,
    either ``export_to=(host, port, stream_id)`` streams the exported
    topics over a :class:`repro.net.transport.LaneTransport` bridge to the
    driver-hosted endpoint as they are published (the cross-process
    shape), or ``collect_exports=True`` captures them into the task
    result (the in-process shape).  Both capture exactly the partition's
    publish order; the suite's merge makes the shapes bit-identical.

    With ``scenario.staged`` (explicit ``pipeline=True``, or auto for
    latency-modeling scenarios) the partition runs as a three-stage
    pipeline over queue-backed bus subscriptions:

        read stage    — a prefetch reader thread decodes bag chunks and
                        keeps messages/micro-batches buffered ahead,
        logic stage   — fault profile + user logic on its own lane worker
                        (one lane shared across input topics, so the
                        drop-RNG draw order is exactly the publish order),
        sink stage    — ``RosRecord`` (bag serialization) and a
                        :class:`MetricsTap` (per-record digests) each on
                        their own lane.

    Disk I/O, XLA compute and bag serialization overlap instead of
    alternating; bounded lanes give backpressure; ``bus.drain()`` is the
    end-of-replay barrier that makes the overlap invisible to results.
    ``pipeline=False`` delivers every stage synchronously (the seed
    shape).  Both shapes produce bit-identical outputs and partials.

    Returns (messages_in, messages_out, messages_dropped, output bag image,
    partial metrics, exported messages or None).  The partial metrics —
    per-topic mergeable
    :class:`TopicMetrics` over this partition's *output* — are computed
    here, on the worker, *as outputs stream through the sink stage*: the
    driver combines KB-sized partials instead of re-reading MB-sized
    payload matrices, and the worker no longer re-sweeps its own output
    image at end of task.
    """
    logic = resolve_logic_ref(scenario.user_logic)
    is_import = chunk_range is None
    # import partitions bypass the scenario's own selection: the stream
    # was already filtered to Scenario.imports by the driver, and the
    # provider's topic/time window shaped it
    topics = (None if is_import or scenario.topics is None
              else list(scenario.topics))
    t_start = None if is_import else scenario.start
    t_end = None if is_import else scenario.end
    if isinstance(source, SegmentHandle):
        # arg-spilled image parked in /dev/shm by the driver: one attach
        # and copy-out; the driver's pool still owns the segment, so a
        # retried or speculative attempt re-reads the same handle
        src = Bag.open_read(backend="memory", image=read_segment(source))
    elif isinstance(source, (bytes, bytearray)):
        src = Bag.open_read(backend="memory", image=bytes(source))
    else:
        src = Bag.open_read(source, backend="disk")
    if scenario.use_memory_cache:
        # materialise the (filtered) partition into the ROSBag cache (§3.2):
        # wholly selected chunks as bytes, cut ones record by record
        tr = otrace.TRACER
        slot = tr.begin("bag.cache_fill", "play") if tr is not None else None
        fill = src.selection_image(topics=topics, start=t_start, end=t_end,
                                   chunk_range=chunk_range)
        play_bag = Bag.open_read(backend="memory", image=fill.image)
        if slot is not None:
            otrace.Tracer.set_attrs(slot, {
                "messages": play_bag.num_messages, "bytes": len(fill.image),
                "raw_chunks": fill.raw_chunks,
                "decoded_chunks": fill.decoded_chunks})
            otrace.Tracer.end(slot)
        play = dict(chunk_range=None, topics=None, start=None, end=None)
        input_topics = play_bag.indexed_topics
    else:
        play_bag = src
        play = dict(chunk_range=chunk_range, topics=topics,
                    start=t_start, end=t_end)
        input_topics = ([t for t in src.topics if t in topics]
                        if topics is not None else src.topics)

    staged = scenario.staged
    mode = "queued" if staged else "sync"
    depth = scenario.queue_depth
    bus = MessageBus()
    out_bag = Bag.open_write(backend="memory")
    # record everything the user logic publishes, but not the replayed
    # inputs; in batched mode the recorder rides the batch subscription so
    # no per-message callback remains on the replay hot path
    rec = RosRecord(bus, out_bag, topics=None, exclude_topics=src.topics,
                    batch=scenario.batch_size is not None,
                    mode=mode, queue_maxsize=depth)
    # metrics ride the sink stage: per-record digests accumulate as outputs
    # stream past, so partials are ready at drain (no output-image re-sweep);
    # input-topic exclusion is enforced bus-side (sink_kw below)
    tap = MetricsTap(engine=metrics_engine, ts_sketch=scenario.ts_sketch)

    n_out = 0
    n_drop = 0
    # deterministic fault profile, decorrelated across shards + partitions
    # (crc32, not hash(): str hashing is per-process randomized); import
    # partitions seed from their rng_tag so the draw sequence is invariant
    # to how the stream was shipped (inline bytes vs spill path)
    tag = rng_tag if rng_tag is not None else (
        source if isinstance(source, str) else "<memory>")
    lo, hi = chunk_range if chunk_range is not None else (0, 0)
    rng = random.Random(scenario.seed * 1_000_003
                        + zlib.crc32(tag.encode()) * 131
                        + lo * 8191 + hi)
    drop = scenario.drop_rate

    # chaos: captured ONCE per partition — the common no-chaos case costs
    # a single global read here and one None check per delivery
    chaos_plan = chaos.active_plan()

    # one shared "logic" lane across all input topics: the drop-RNG draw
    # order (and hence the output stream) is exactly the synchronous one.
    # The tap excludes input topics bus-side, so replay traffic is never
    # even enqueued toward the metrics sink.
    logic_kw = dict(mode=mode, maxsize=depth, group="logic")
    sink_kw = dict(mode=mode, maxsize=depth, group="metrics",
                   exclude_topics=src.topics)
    # logic-stage tracing: one span per micro-batch in batched mode;
    # per-message mode emits one chunk-level ``logic.step`` span per
    # TRACE_CHUNK callbacks (two clock reads per message when enabled,
    # zero when disabled) so the hot path never pays per-message spans
    _ls = [0, 0, 0]                      # chunk t0, callbacks, busy ns

    def _flush_logic(now: int) -> None:
        tr = otrace.TRACER
        if tr is not None and _ls[1]:
            tr.emit("logic.step", "logic", _ls[0], now,
                    attrs={"n": _ls[1], "busy_ns": _ls[2]})
        _ls[0] = _ls[1] = _ls[2] = 0

    def _logic_tick(t0: int) -> None:
        now = time.perf_counter_ns()
        if _ls[0] == 0:
            _ls[0] = t0
        _ls[1] += 1
        _ls[2] += now - t0
        if _ls[1] >= TRACE_CHUNK:
            _flush_logic(now)

    if scenario.batch_size is None:
        def on_msg(msg: Message) -> None:
            nonlocal n_out, n_drop
            t0 = (time.perf_counter_ns()
                  if otrace.TRACER is not None else 0)
            try:
                if drop and rng.random() < drop:
                    n_drop += 1
                    return
                if scenario.latency_model_s:
                    time.sleep(scenario.latency_model_s)  # simulated model
                if chaos_plan is not None and chaos_plan.probe(
                        "logic_raise", scenario.name) is not None:
                    raise chaos.ChaosFault(
                        f"injected user-logic failure in {scenario.name!r}")
                out = logic(msg)
                if out is not None:
                    topic, data = out
                    bus.advertise(topic).publish(msg.timestamp, data)
                    n_out += 1
            finally:
                if t0:
                    _logic_tick(t0)

        for t in input_topics:
            bus.subscribe(t, on_msg, **logic_kw)
        bus.subscribe(None, tap.on_message, **sink_kw)
    else:
        def on_batch(msgs: list[Message]) -> None:
            nonlocal n_out, n_drop
            tr = otrace.TRACER
            slot = None
            if tr is not None:
                # the step's own spans (perception.step, .readback) nest
                slot = tr.begin("logic.step", "logic", attrs={"n": len(msgs)})
                tr.push(otrace.Tracer.span_id(slot))
            try:
                if drop:
                    kept = [m for m in msgs if rng.random() >= drop]
                    n_drop += len(msgs) - len(kept)
                    msgs = kept
                    if not msgs:
                        return
                if scenario.latency_model_s:
                    time.sleep(scenario.latency_model_s)  # one step/batch
                if chaos_plan is not None and chaos_plan.probe(
                        "logic_raise", scenario.name) is not None:
                    raise chaos.ChaosFault(
                        f"injected user-logic failure in {scenario.name!r}")
                outs = logic(msgs)
                if outs:
                    out_msgs = [Message(t, ts, d) for t, ts, d in outs]
                    bus.publish_batch(out_msgs)
                    n_out += len(out_msgs)
            finally:
                if slot is not None:
                    tr.pop()
                    otrace.Tracer.end(slot)

        for t in input_topics:
            bus.subscribe_batch(t, on_batch, **logic_kw)
        bus.subscribe_batch(None, tap.on_batch, **sink_kw)

    # export routing: the exported topics leave this partition either over
    # a transport bridge (cross-process shape: streamed to the driver's
    # endpoint as they are published) or through a synchronous capture
    # returned with the result (in-process shape).  Both observe exactly
    # the publish order.
    exported: Optional[list[Message]] = None
    bridge = None
    export_topics = sorted(scenario.exports or ())
    if export_topics and export_to is not None:
        from repro.net.transport import LaneTransport
        # 4th element (use the same-host shm ring) is optional so older
        # 3-tuple callers keep the pure-TCP shape
        host, port, stream_id = export_to[:3]
        use_shm = bool(export_to[3]) if len(export_to) > 3 else False
        transport = LaneTransport.connect((host, port), stream_id=stream_id,
                                          shm=use_shm)
        bridge = bus.bridge(export_topics, transport,
                            maxsize=scenario.queue_depth)
    elif export_topics and collect_exports:
        exported = []
        for t in export_topics:
            bus.subscribe(t, exported.append)

    rec.start()
    player = RosPlay(play_bag, bus, **play)
    close_slot = None
    try:
        if scenario.batch_size is None:
            n_in = player.run(prefetch=MESSAGE_PREFETCH if staged else 0)
        else:
            # double-buffered framing: the bag-chunk reader thread keeps
            # the next micro-batch decoded while this one is in flight
            n_in = player.run_batched(scenario.batch_size,
                                      prefetch=2 if staged else 0)
        # the partition's end: stage barriers, recorder stop, output image
        # and metric partials
        tr = otrace.TRACER
        close_slot = (tr.begin("partition.close", "record")
                      if tr is not None else None)
        bus.drain()         # barrier: every stage flushed, errors surface
        _flush_logic(time.perf_counter_ns())    # close the last logic chunk
        if bridge is not None:
            bridge.drain()  # cross-wire barrier: the collector has the
            #                 full stream before this task can report
        rec.stop()          # surfaces deferred recorder write errors
    finally:
        if bridge is not None:
            try:
                bridge.close()
            except BaseException:  # noqa: BLE001 - drain above is the
                pass               # barrier; close is best-effort release
        try:
            rec.stop()      # no-op when already stopped (exception-safe)
        except BaseException:   # noqa: BLE001 - the drain/stop error above
            pass                # is the one that must propagate
        bus.close()         # always stop lane workers — no thread leak
        src.close()         # and never leak bag handles on a failed task
        if scenario.use_memory_cache:
            play_bag.close()
    out_bag.close()
    # image() is close-safe by contract (captured at close time) — the
    # use-after-close here was a latent bug before MemoryChunkedFile.close
    # consolidated the image
    image = out_bag.chunked_file.image()
    partials = tap.finalize()
    if close_slot is not None:
        otrace.Tracer.end(close_slot)
    return n_in, n_out, n_drop, image, partials, exported


def _run_scenario_aggregate(aggregator: Aggregator, scenario_name: str,
                            sources: Sequence,
                            partials: Sequence[dict],
                            golden_path: Optional[str],
                            messages_in: int) -> tuple[bytes, Verdict]:
    """One worker task: the aggregation stage of one scenario.

    Merges the (shard, partition)-ordered output sources into one
    timestamp-ordered bag, folds the worker-computed partial metrics
    (no payload re-sweep), compares against the golden bag, and returns
    ``(merged image, verdict)``.  ``sources`` are memory-bag images *or
    spill paths* (see ``ProcessBackend.spill_arg``): on the process
    backend the driver parks each partition image in the backend's spill
    dir and ships only the path, so the worker merges through streaming
    index-only disk readers and MB-sized images never ride the task pipe
    in either direction.  Scheduled on the shared pool with lineage stage
    ``"aggregate"`` so it overlaps remaining replay work and gets the
    scheduler's full retry/speculation semantics — spill files outlive
    the task (the backend reaps them at shutdown), so recompute is safe.
    """
    with otrace.span("aggregate.merge", "agg",
                     attrs={"scenario": scenario_name,
                            "sources": len(sources)}):
        merged, verdict = aggregator.aggregate(
            scenario_name, sources, golden=golden_path,
            messages_in=messages_in, partials=list(partials))
        image = merged.chunked_file.image()
        merged.close()
    return image, verdict


def _run_partition(bag_path: str, chunk_range: tuple[int, int],
                   user_logic: UserLogic, use_memory_cache: bool,
                   latency_model_s: float = 0.0) -> tuple[int, int, bytes]:
    """Seed-compatible single-partition entry point (per-message replay).

    Returns (messages_in, messages_out, output bag image).
    """
    sc = Scenario(name="partition", bag_path=bag_path, user_logic=user_logic,
                  latency_model_s=latency_model_s,
                  use_memory_cache=use_memory_cache)
    n_in, n_out, _, image, _, _ = _run_scenario_partition(sc, bag_path,
                                                          chunk_range)
    return n_in, n_out, image


def _selection_matches_nothing(src: Bag, sc: Scenario) -> bool:
    """True when the scenario's topic filter / time window provably selects
    zero messages of ``src`` (from the chunk index alone).  Such shards get
    no tasks at all — an empty selection is a clean zero-message report and
    a vacuous PASS, not a degenerate partition plan."""
    if not src.num_chunks:
        return True
    if sc.topics is not None and not (set(sc.topics) & set(src.topics)):
        return True
    if sc.start is not None or sc.end is not None:
        for info in src.chunk_infos():
            if sc.start is not None and info.t_max < sc.start:
                continue
            if sc.end is not None and info.t_min >= sc.end:
                continue
            return False
        return True
    return False


class ScenarioSuite:
    """Run a whole catalog of heterogeneous scenarios through ONE scheduler
    and score the results through the aggregation layer.

    Every shard of every scenario is partitioned independently (its own
    ``num_partitions`` per shard, default = ``num_workers``), all
    partitions are submitted up front, and the shared worker pool — thread
    or process backend — drains the matrix with the scheduler's full
    fault-tolerance/speculation semantics.  Shards whose topic filter /
    time window provably selects nothing are pruned at planning time.

    Aggregation is itself scheduled: the moment a scenario's last replay
    partition reports, its merge + metrics + golden-compare run as one
    ordinary task (lineage stage ``"aggregate"``) on the same pool,
    overlapping the other scenarios' remaining replay work instead of
    running serially on the driver after the drain.  Workers ship partial
    per-topic metrics (KBs) next to each partition image, so the metric
    stage is a pure combine — the driver never re-reads payload bytes,
    and per-task results are discarded as soon as they are consumed.

    ``run`` returns ``{scenario.name: Verdict}``: each verdict carries the
    golden-comparison outcome (or an unconditional pass when the scenario
    has no golden bag), per-topic metrics, and the full
    :class:`SimulationReport` — whose ``output_image`` is the merged,
    timestamp-ordered output of all shards, whose ``wall_time_s`` spans
    suite start to the scenario's last finished partition, and whose
    ``scheduler_stats`` are the shared pool's counters.

    Scenarios may be wired together through the **distributed message
    pool**: a scenario's ``exports`` topics feed every scenario that
    ``imports`` them.  The suite plans the routing graph (validated as a
    single-exporter DAG), and when a provider's last partition reports,
    its per-partition export streams — concatenated in deterministic
    (shard, partition) order and stably time-sorted — become the
    importer's *import partition*: one extra task replaying the merged
    stream through the importer's user logic, submitted the moment all
    of its providers are final.  ``export_transport`` picks the carrier:
    ``"inline"`` rides exports on task results, ``"wire"`` streams them
    over :mod:`repro.net` LaneTransports to a backend-hosted
    :class:`~repro.net.transport.RemoteBus` collector (with credit-based
    backpressure and drain barriers), ``"shm"`` is wire with the
    same-host shared-memory ring negotiated per stream (frames bypass
    the TCP stack; falls back to TCP framing when the handshake
    declines), and ``"auto"`` (default) routes out-of-band exactly where
    results would otherwise ride the process-backend pipe, preferring
    shm > wire.  Outputs, checksums and verdicts are bit-identical
    across carriers and backends — ``benchmarks/transport.py`` and
    ``benchmarks/shm.py`` assert it every run; each verdict records
    which carrier actually ran in ``Verdict.transport``.

    ``on_scheduler`` (if given) is called with the live Scheduler right
    after submission — the hook fault-injection harnesses use to kill
    workers / add elastic capacity mid-suite.  ``aggregator`` overrides
    the default exact-matching :class:`Aggregator`.

    ``run(verdict_log=path)`` additionally appends one JSONL record per
    scenario (name, verdict, metric checksums, timings) to ``path`` and
    rewrites a suite manifest (scenario → golden path → verdict) next to
    it — the CI-native face of the regression harness.

    ``run(cache=...)`` (a :class:`repro.cache.ResultCache` or a store
    root path) turns on the **content-addressed result cache**: at
    planning time each scenario's key — bag content digests + parameter
    fingerprint + logic version + kernel/interpret config + provider
    keys (ARCHITECTURE.md §9) — is probed against the store, and every
    hit is pruned from scheduling entirely: its verdict, metrics, merged
    output image and export stream rehydrate from the entry, so an
    unchanged suite re-run costs a digest sweep and a metadata read
    instead of a replay.  Misses replay normally and bank their outcome.
    Replay here is bit-identical across backends/carriers/shapes, which
    is what makes a cached result substitutable for a recomputed one;
    each verdict carries ``cache="hit"|"miss"`` provenance (persisted to
    the JSONL log and manifest), and ``last_cache_stats`` exposes the
    run's hit/miss/put counters.  Corrupt or truncated entries read as
    misses — the cache can cost a replay, never a suite.

    ``on_error`` picks the failure model (ARCHITECTURE.md §10).  The
    default ``"raise"`` keeps the historical semantics: the first
    perma-failed task fails the whole run.  ``"degrade"`` runs the
    scheduler in quarantine mode instead — a scenario whose partition
    (or aggregation) perma-fails degrades to a
    ``Verdict(status="ERROR")`` carrying the cause string, every
    scenario downstream of a failed *exporter* in the routing DAG gets
    an ERROR with the upstream lineage, and everything else completes
    bit-identically to a clean run.  ERROR verdicts are never banked in
    the result cache and ride into the verdict JSONL/manifest like any
    other status.
    """

    def __init__(self, scenarios: Sequence[Scenario], num_workers: int = 4,
                 backend: Union[str, ExecutorBackend] = "thread",
                 scheduler_kwargs: Optional[dict] = None,
                 on_scheduler: Optional[Callable[[Scheduler], None]] = None,
                 aggregator: Optional[Aggregator] = None,
                 export_transport: str = "auto",
                 on_error: str = "raise"):
        names = [s.name for s in scenarios]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate scenario names in {names}")
        if export_transport not in ("auto", "shm", "wire", "inline"):
            raise ValueError(f"unknown export_transport {export_transport!r}")
        if on_error not in ("raise", "degrade"):
            raise ValueError(f"unknown on_error {on_error!r}")
        self.scenarios = list(scenarios)
        self.num_workers = num_workers
        self.backend = backend
        self.scheduler_kwargs = scheduler_kwargs or {}
        self.on_scheduler = on_scheduler
        self.aggregator = aggregator or Aggregator()
        self.export_transport = export_transport
        self.on_error = on_error
        #: hit/miss/put counters of the last ``run(cache=...)``; None when
        #: the last run had no cache
        self.last_cache_stats: Optional[dict] = None

    def _plan_routing(self) -> tuple[list[set], list[set]]:
        """Resolve ``Scenario.exports``/``imports`` into the routing graph.

        Returns ``(needs, consumers)``: ``needs[i]`` is the set of
        scenario indices ``i`` imports from, ``consumers[j]`` the set fed
        by ``j``.  Validates that every imported topic has exactly one
        exporter, nothing self-imports, and the graph is a DAG — a cycle
        would deadlock the suite (each side waiting for the other's
        exports), so it fails at planning time instead.
        """
        providers: dict[str, int] = {}
        for i, sc in enumerate(self.scenarios):
            for t in sc.exports or ():
                if t in providers:
                    raise ValueError(
                        f"topic {t!r} exported by both "
                        f"{self.scenarios[providers[t]].name!r} and "
                        f"{sc.name!r}; each topic has one exporter")
                providers[t] = i
        needs: list[set] = [set() for _ in self.scenarios]
        consumers: list[set] = [set() for _ in self.scenarios]
        for i, sc in enumerate(self.scenarios):
            for t in sc.imports or ():
                j = providers.get(t)
                if j is None:
                    raise ValueError(f"scenario {sc.name!r} imports {t!r} "
                                     "which no scenario exports")
                if j == i:
                    raise ValueError(
                        f"scenario {sc.name!r} imports its own export {t!r}")
                needs[i].add(j)
                consumers[j].add(i)
        state = [0] * len(self.scenarios)     # 0 unseen / 1 visiting / 2 done

        def visit(i: int) -> None:
            if state[i] == 1:
                raise ValueError(
                    f"routing cycle through scenario "
                    f"{self.scenarios[i].name!r}: imports must form a DAG")
            if state[i]:
                return
            state[i] = 1
            for j in needs[i]:
                visit(j)
            state[i] = 2

        for i in range(len(self.scenarios)):
            visit(i)
        return needs, consumers

    def _resolve_export_transport(self, backend_name: str) -> str:
        """``"auto"`` routes exports out-of-band exactly where they would
        otherwise ride the task-result pipe (the process backend),
        preferring the same-host shm ring over loopback TCP when the host
        supports it (shm > wire > inline); in-process thread workers hand
        the driver a reference instead.  ``"shm"`` asks transports to
        negotiate the ring but still degrades per-stream to TCP framing
        when the handshake declines.  All shapes are bit-identical, so
        the choice is pure mechanics."""
        if self.export_transport != "auto":
            return self.export_transport
        if backend_name != "process":
            return "inline"
        return "shm" if shm_available() else "wire"

    def _plan_cache_keys(self, cache, needs: list[set]) -> list:
        """Per-scenario result-cache keys; ``None`` marks an uncacheable
        scenario (non-addressable user logic — or one anywhere upstream
        of it, since an importer's inputs include its providers' exports).

        Keys are pure functions of configuration and bag *content*:
        logic version + kernel/interpret config + aggregator tolerance +
        ``Scenario.fingerprint()`` + per-shard bag digests + the golden
        bag digest + (recursively) the providers' keys — so a change
        anywhere upstream in the routing DAG invalidates every scenario
        downstream.  Any I/O or digest failure degrades that scenario to
        uncacheable rather than failing the suite.
        """
        keys: list = [None] * len(self.scenarios)
        done = [False] * len(self.scenarios)

        def key_of(i: int):
            if done[i]:
                return keys[i]
            done[i] = True
            sc = self.scenarios[i]
            try:
                fp = sc.fingerprint()
                provider_keys = []
                for j in sorted(needs[i]):
                    kj = key_of(j)
                    if kj is None:
                        return None
                    provider_keys.append(kj)
                digests = [cache.bag_digest(p) for p in sc.shard_paths]
                golden = (cache.bag_digest(sc.golden_bag_path)
                          if sc.golden_bag_path is not None else None)
                keys[i] = cache.scenario_key(
                    fp, digests, golden, provider_keys,
                    tolerance=self.aggregator.tolerance)
            except (OSError, ValueError):
                keys[i] = None
            return keys[i]

        for i in range(len(self.scenarios)):
            key_of(i)
        return keys

    def _plan(self, sc: Scenario) -> list[tuple[int, str, tuple[int, int]]]:
        """One (shard index, shard path, chunk range) triple per task."""
        tasks: list[tuple[int, str, tuple[int, int]]] = []
        for si, shard in enumerate(sc.shard_paths):
            src = Bag.open_read(shard, backend="disk")
            if _selection_matches_nothing(src, sc):
                src.close()
                continue
            parts = partition_bag(src, sc.num_partitions or self.num_workers)
            src.close()
            tasks.extend((si, shard, pr) for pr in parts)
        return tasks

    @staticmethod
    def _resolve_metrics_engine(sc: Scenario, backend_name: str) -> str:
        """Pick the partition sink's digest engine.  Process workers are
        pinned to the fork-safe numpy engine (never init jax in a forked
        child of a jax-loaded driver); in-process, ``"auto"`` makes the
        fused Pallas consume step the stock batched shape and numpy the
        per-message one.  All engines are bit-identical, so this choice
        can never move a checksum or a verdict."""
        if backend_name == "process":
            return "numpy"
        if sc.metrics_engine == "auto":
            return "fused" if sc.batch_size is not None else "numpy"
        return sc.metrics_engine

    def run(self, timeout: float = 300.0,
            verdict_log: Optional[str] = None,
            manifest_path: Optional[str] = None,
            cache=None,
            trace: Optional[str] = None) -> dict[str, Verdict]:
        """Drive every scenario to a verdict (see class docstring).

        ``trace=<path>`` records the run with the :mod:`repro.obs`
        tracer and writes a Chrome/Perfetto-loadable ``trace.json`` to
        ``path`` when the suite finishes (also on failure — the flight
        recorder matters most when a run dies): one stitched timeline of
        driver and worker spans across scheduler, lanes, replay, logic,
        transport, shm and cache seams.  Per-scenario per-stage
        durations derived from the trace ride into the verdict JSONL,
        and a ``repro.obs.metrics`` snapshot into the manifest.
        """
        for sc in self.scenarios:
            # fail before burning replay time, not at aggregation
            if (sc.golden_bag_path is not None
                    and not os.path.exists(sc.golden_bag_path)):
                raise FileNotFoundError(
                    f"scenario {sc.name!r}: golden bag "
                    f"{sc.golden_bag_path!r} does not exist")
        plans = [(sc, self._plan(sc)) for sc in self.scenarios]
        needs, consumers = self._plan_routing()

        # -- flight recorder --------------------------------------------
        # own_trace: this run installed the tracer and tears it down; a
        # pre-enabled tracer (a benchmark harness) is borrowed instead.
        # Setup precedes the cache probe so cache.load spans are captured.
        own_trace = False
        suite_tracer: Optional[otrace.Tracer] = None
        suite_slot = None
        trace_out: dict = {}            # filled once by _finish_trace
        if trace is not None:
            own_trace = not otrace.enabled()
            if own_trace:
                otrace.enable(root_name="suite")
            suite_tracer = otrace.get_tracer()
            suite_slot = suite_tracer.begin(
                "suite.run", "suite",
                attrs={"scenarios": [sc.name for sc in self.scenarios]})
            suite_tracer.push(otrace.Tracer.span_id(suite_slot))

        def _finish_trace() -> None:
            # idempotent: the normal path calls it after the cache-put
            # sweep (so the stage breakdown rides into the verdict log);
            # the crash path reaches it from the finally below — a
            # partial trace is the whole point of a flight recorder
            nonlocal suite_tracer
            if suite_tracer is None:
                return
            tr, suite_tracer = suite_tracer, None
            from repro.obs import export as obs_export
            tr.pop()
            otrace.Tracer.end(suite_slot)
            records = tr.drain_all()
            trace_out["stages"] = obs_export.stage_breakdown(records)
            trace_out["spans"] = len(records)
            try:
                obs_export.write_trace(trace, records,
                                       driver_pid=os.getpid())
            finally:
                if own_trace:
                    otrace.disable()

        # -- result cache probe (the unchanged-suite hot path) ----------
        # a hit scenario contributes ZERO tasks: its verdict, metrics,
        # merged image and export stream rehydrate from the store, and
        # the suite only schedules what actually changed
        encode_stream = decode_stream = _CachedResult = None
        cache_keys: list = [None] * len(self.scenarios)
        cached: list = [None] * len(self.scenarios)
        if cache is not None:
            from repro.cache import CachedResult as _CachedResult
            from repro.cache import (ResultCache,
                                     decode_message_stream as decode_stream,
                                     encode_message_stream as encode_stream)
            if not isinstance(cache, ResultCache):
                cache = ResultCache(cache)
            cache_keys = self._plan_cache_keys(cache, needs)
            for i, key in enumerate(cache_keys):
                if key is None:
                    continue
                if not plans[i][1] and not needs[i]:
                    # pruned-empty scenario: the vacuous verdict is
                    # cheaper to recompute than to round-trip
                    cache_keys[i] = None
                    continue
                cached[i] = cache.load(
                    key, require_exports=bool(consumers[i]
                                              and self.scenarios[i].exports))
        self.last_cache_stats = None

        t0 = time.monotonic()
        # tid -> (scenario i, (shard j, partition k)) for result assembly;
        # an importing scenario's import partition carries key (-1, 0) so
        # the import-stream output merges first, deterministically
        owner: dict[int, tuple[int, tuple[int, int]]] = {}
        pending = [0 if cached[i] is not None
                   else len(tasks) + (1 if needs[i] else 0)
                   for i, (_, tasks) in enumerate(plans)]
        total_tasks = list(pending)
        # scenario i -> (shard, partition) -> (image, partial metrics);
        # released to the aggregation task as soon as the scenario drains
        parts: list[Optional[dict]] = [{} for _ in plans]
        counts = [[0, 0, 0] for _ in plans]      # in / out / dropped
        # degraded-mode failure ledger: cause string per errored scenario
        scn_error: list[Optional[str]] = [None] * len(plans)
        # export-carrier provenance per scenario ("shm"/"wire"/"inline";
        # None = exports nothing, or rehydrated from the result cache)
        scn_transport: list[Optional[str]] = [None] * len(plans)
        degrade = self.on_error == "degrade"
        sched_kwargs = dict(self.scheduler_kwargs)
        if degrade:
            # poison tasks surrender instead of failing the job; the
            # failure is delivered through on_task_failed below and the
            # scenario that owned it degrades to an ERROR verdict
            sched_kwargs.setdefault("quarantine", True)
        replay_end = [0.0 for _ in plans]        # last replay-task finish
        agg_owner: dict[int, int] = {}           # aggregation tid -> i
        agg_out: dict[int, tuple[bytes, Verdict]] = {}
        # every driver-side spill reference still live (temp-file path or
        # shm SegmentHandle); the finally sweep is the error-path cleanup,
        # per-completion reclaims the eager one
        tracked_spills: set = set()
        reclaim_holder: list[Callable] = []

        try:
            with Scheduler(num_workers=self.num_workers,
                           backend=self.backend,
                           **sched_kwargs) as sched:
                backend_name = sched.backend.name
                if backend_name == "process":
                    jitted = [sc.name for sc in self.scenarios
                              if isinstance(sc.user_logic, str)
                              and sc.user_logic.startswith("perception://")]
                    if jitted:
                        # forked workers must never initialise jax (the
                        # driver is jax-loaded; fork + XLA threads can
                        # deadlock) — fail loudly instead of hanging
                        raise ValueError(
                            f"scenarios {jitted} use perception:// logic, "
                            "which is jitted and cannot run on the process "
                            "backend; use the thread backend")
                pool_agg = self.aggregator
                if backend_name == "process" and pool_agg.engine != "numpy":
                    # never initialize jax inside a forked worker of a
                    # jax-loaded driver (deadlock risk) — the numpy engine
                    # is bit-identical, so the downgrade can't move a
                    # verdict
                    pool_agg = Aggregator(tolerance=pool_agg.tolerance,
                                          metric_batch=pool_agg.metric_batch,
                                          engine="numpy")

                # spill-aware dispatch: on backends with an argument spill
                # (process), large partition images / import streams are
                # parked out-of-band and tasks get references — a shm
                # SegmentHandle (one memcpy each way) or a temp-file path
                # (streaming disk readers) — so the driver never pickles
                # bulk bytes through the pipe
                spill_arg = getattr(sched.backend, "spill_arg", None)
                spill_bytes = getattr(sched.backend, "spill_bytes", None)
                reclaim = getattr(sched.backend, "reclaim_spill", None)
                if reclaim is not None:
                    reclaim_holder.append(reclaim)

                def spill_source(data: bytes
                                 ) -> "bytes | str | SegmentHandle":
                    if (spill_arg is None or spill_bytes is None
                            or len(data) <= spill_bytes):
                        return data
                    path = spill_arg(data)
                    tracked_spills.add(path)
                    return path

                def reclaim_paths(paths) -> None:
                    for p in paths:
                        tracked_spills.discard(p)
                        if reclaim is not None:
                            reclaim(p)

                # -- export routing state -------------------------------
                resolved_transport = \
                    self._resolve_export_transport(backend_name)
                wire = (resolved_transport in ("wire", "shm")
                        and any(consumers))
                use_shm = resolved_transport == "shm"
                collect_lock = threading.Lock()
                # (scenario i, partition key) -> committed export stream
                collected: dict[tuple[int, tuple[int, int]],
                                list[Message]] = {}
                stream_key: dict[str, tuple[int, tuple[int, int]]] = {}
                ep_addr: Optional[tuple[str, int]] = None
                if wire:
                    # the backend hosts the listener; partitions bridge
                    # their exported topics here over LaneTransports.
                    # Streams commit at each DRAIN barrier, which the
                    # partition passes before reporting — so a committed
                    # stream is always complete, and a crashed attempt's
                    # partial stream is never committed (its retry's is)
                    def export_sink(stream_id: str, msgs) -> None:
                        with collect_lock:
                            collected[stream_key[stream_id]] = list(msgs)
                    ep_addr = sched.backend.host_endpoint(sink=export_sink)
                    # the endpoint just hosted: its stream_carriers map is
                    # the transport-provenance source of truth per stream
                    ep_obj = sched.backend.endpoints[-1]
                # scenario i -> partition keys expected to export
                export_keys: dict[int, list[tuple[int, int]]] = {}
                exports_inline: dict[tuple[int, tuple[int, int]],
                                     list[Message]] = {}
                exports_of: dict[int, list[Message]] = {}
                # cache-hit importers never submit an import partition;
                # seeding them here also lets providers release streams
                # once every *live* importer has consumed
                submitted_imports: set = {i for i in range(len(plans))
                                          if cached[i] is not None}
                # encoded export streams captured for store writes
                export_snaps: dict[int, bytes] = {}
                agg_spills: dict[int, list[str]] = {}
                spill_by_tid: dict[int, list[str]] = {}

                def register_export_stream(i: int, key: tuple[int, int],
                                           ) -> tuple[Optional[tuple],
                                                      bool]:
                    """(export_to, collect_exports) for one partition of
                    an exporting scenario, registering its stream id."""
                    export_keys.setdefault(i, []).append(key)
                    if not wire:
                        return None, True
                    sid = f"{plans[i][0].name}#{key[0]}#{key[1]}"
                    stream_key[sid] = (i, key)
                    return (ep_addr[0], ep_addr[1], sid, use_shm), False

                def submit_aggregate(i: int) -> None:
                    sc = plans[i][0]
                    rows = parts[i]
                    ordered = sorted(rows)   # (shard, partition): merge
                    sources = [spill_source(rows[k][0])  # deterministic
                               for k in ordered]
                    partials = [rows[k][1] for k in ordered]
                    agg_spills[i] = [s for s in sources
                                     if isinstance(s, (str, SegmentHandle))]
                    tid = sched.submit(
                        _run_scenario_aggregate, pool_agg, sc.name,
                        sources, partials, sc.golden_bag_path,
                        counts[i][0], lineage=("aggregate", sc.name))
                    agg_owner[tid] = i
                    parts[i] = None          # driver drops its references

                def collect_export_stream(j: int) -> list[Message]:
                    """The scenario's full export stream: per-partition
                    streams concatenated in deterministic (shard,
                    partition) order, then stably time-sorted — identical
                    whichever transport shape carried them."""
                    msgs: list[Message] = []
                    for key in sorted(export_keys.get(j, [])):
                        if wire:
                            with collect_lock:
                                msgs.extend(collected.pop((j, key), ()))
                        else:
                            msgs.extend(exports_inline.pop((j, key), ()))
                    msgs.sort(key=lambda m: m.timestamp)
                    return msgs

                def finish_exports(j: int) -> None:
                    exports_of[j] = collect_export_stream(j)
                    if cache_keys[j] is not None:
                        # snapshot before importers consume + release: the
                        # store entry must carry the committed stream so a
                        # future importer downstream of this (cached)
                        # exporter can still replay
                        export_snaps[j] = encode_stream(exports_of[j])
                    for i in sorted(consumers[j]):
                        maybe_submit_import(i)

                def maybe_submit_import(i: int) -> None:
                    """Submit scenario i's import partition once every
                    provider's export stream is final."""
                    if i in submitted_imports:
                        return
                    if any(j not in exports_of for j in needs[i]):
                        return
                    submitted_imports.add(i)
                    sc = plans[i][0]
                    want = set(sc.imports or ())
                    msgs = [m for j in sorted(needs[i])
                            for m in exports_of[j] if m.topic in want]
                    msgs.sort(key=lambda m: m.timestamp)    # stable merge
                    cache = Bag.open_write(backend="memory")
                    for m in msgs:
                        cache.write_message(m)
                    cache.close()
                    source = spill_source(cache.chunked_file.image())
                    engine = self._resolve_metrics_engine(sc, backend_name)
                    key = (-1, 0)
                    export_to, collect = ((None, False) if not consumers[i]
                                          else register_export_stream(i,
                                                                      key))
                    tid = sched.submit(
                        _run_scenario_partition, sc, source, None, engine,
                        export_to, f"<imports:{sc.name}>", collect,
                        lineage=("scenario", sc.name, -1, "<imports>",
                                 0, 0))
                    owner[tid] = (i, key)
                    if isinstance(source, (str, SegmentHandle)):
                        spill_by_tid[tid] = [source]
                    # release provider streams every importer has now
                    # consumed — driver residency stays O(in-flight
                    # routing), matching the parts[i]=None discipline
                    for j in sorted(needs[i]):
                        if consumers[j] <= submitted_imports:
                            exports_of[j] = []

                def fail_scenario(i: int, cause: str) -> None:
                    """Degrade scenario i to ERROR and cascade through the
                    routing DAG: an importer of a failed exporter can never
                    see a complete input stream, so it errors too (with the
                    upstream lineage in its cause).  Cache-hit consumers
                    are immune — they rehydrate, they never replay."""
                    if scn_error[i] is not None:
                        return
                    scn_error[i] = cause
                    parts[i] = None          # drop partial partition images
                    reclaim_paths(agg_spills.pop(i, ()))
                    # a failed scenario never submits its import partition;
                    # marking it "submitted" also lets providers release
                    # streams no live importer is still waiting on
                    submitted_imports.add(i)
                    name = plans[i][0].name
                    for c in sorted(consumers[i]):
                        if cached[c] is not None:
                            continue
                        fail_scenario(
                            c, f"upstream scenario {name!r} errored: "
                               f"{cause}")

                def on_task_failed(tid: int, error) -> None:
                    # quarantine delivery: a task burned max_attempts.
                    # Replay-partition failures poison the whole scenario
                    # (and its DAG downstream); an aggregation failure
                    # degrades only its own verdict — the exports were
                    # committed at the drain barrier before the aggregate
                    # was even submitted, so downstream inputs are sound.
                    reclaim_paths(spill_by_tid.pop(tid, ()))
                    sched.discard(tid)
                    if tid in owner:
                        i, _key = owner[tid]
                        fail_scenario(i, str(error))
                    else:
                        i = agg_owner[tid]
                        reclaim_paths(agg_spills.pop(i, ()))
                        if scn_error[i] is None:
                            scn_error[i] = str(error)

                def on_task_done(tid: int, result) -> None:
                    if tid in owner:
                        i, key = owner[tid]
                        if scn_error[i] is not None:
                            # straggler partition of an already-degraded
                            # scenario: release and forget
                            sched.discard(tid)
                            reclaim_paths(spill_by_tid.pop(tid, ()))
                            return
                        n_in, n_out, n_drop, image, partial, exported = \
                            result
                        counts[i][0] += n_in
                        counts[i][1] += n_out
                        counts[i][2] += n_drop
                        parts[i][key] = (image, partial)
                        if consumers[i] and not wire:
                            exports_inline[(i, key)] = exported or []
                        end = sched.task_finished_at(tid)
                        if end is not None:
                            replay_end[i] = max(replay_end[i], end)
                        sched.discard(tid)
                        reclaim_paths(spill_by_tid.pop(tid, ()))
                        pending[i] -= 1
                        if pending[i] == 0:
                            # the scenario's last partition just reported:
                            # its aggregation overlaps the other
                            # scenarios' remaining replay work on the
                            # same pool, and its export stream is final —
                            # importers waiting on it can now be planned
                            submit_aggregate(i)
                            if consumers[i]:
                                finish_exports(i)
                    else:
                        i = agg_owner[tid]
                        agg_out[i] = result
                        sched.discard(tid)
                        reclaim_paths(agg_spills.pop(i, ()))

                for i, (sc, tasks) in enumerate(plans):
                    if cached[i] is not None:
                        continue        # rehydrated: no replay tasks at all
                    engine = self._resolve_metrics_engine(sc, backend_name)
                    exporting = bool(consumers[i])
                    part_of_shard: dict[int, int] = {}
                    for si, shard, (lo, hi) in tasks:
                        k = part_of_shard.get(si, 0)
                        part_of_shard[si] = k + 1
                        export_to, collect = ((None, False) if not exporting
                                              else register_export_stream(
                                                  i, (si, k)))
                        tid = sched.submit(
                            _run_scenario_partition, sc, shard, (lo, hi),
                            engine, export_to, None, collect,
                            lineage=("scenario", sc.name, si, shard,
                                     lo, hi))
                        owner[tid] = (i, (si, k))
                # a cache-hit exporter's stream is final at t0: decode it
                # from the store entry and unblock live importers now —
                # this is how a changed importer replays bit-identically
                # downstream of an *unchanged, never-replayed* provider
                for j in range(len(plans)):
                    if cached[j] is None or not consumers[j]:
                        continue
                    if any(cached[c] is None for c in consumers[j]):
                        exports_of[j] = decode_stream(cached[j].export_image)
                        for i in sorted(consumers[j]):
                            maybe_submit_import(i)
                # a pruned-empty exporter produces no tasks, so its
                # (empty) export stream is final now — unblock importers
                # before the run, not never
                for j in range(len(plans)):
                    if (cached[j] is None and consumers[j]
                            and not plans[j][1] and not needs[j]):
                        finish_exports(j)
                if self.on_scheduler is not None:
                    self.on_scheduler(sched)
                sched.run(timeout=timeout, on_task_done=on_task_done,
                          on_task_failed=(on_task_failed if degrade
                                          else None))
                stats = dict(sched.stats)
                # transport provenance, read before the endpoint stops:
                # a wire-mode exporter's streams each negotiated a
                # carrier at HELLO ("shm" only after a ring switch), and
                # a scenario is "shm" only if every stream made the
                # switch — a mixed outcome is reported as the weaker
                # carrier rather than overstated
                for i in range(len(plans)):
                    if not consumers[i] or cached[i] is not None:
                        continue
                    if not wire:
                        scn_transport[i] = "inline"
                        continue
                    got = [c for c in (
                        ep_obj.stream_carriers.get(
                            f"{plans[i][0].name}#{k[0]}#{k[1]}")
                        for k in export_keys.get(i, ())) if c is not None]
                    if got:
                        scn_transport[i] = ("shm" if all(c == "shm"
                                                         for c in got)
                                            else "wire")
        finally:
            # error-path spill cleanup: a failed suite must not leave
            # parked images/import streams behind (the backend's
            # shutdown-time directory reap is the backstop when the
            # scheduler owned the spill dir)
            if tracked_spills and reclaim_holder:
                for p in list(tracked_spills):
                    reclaim_holder[0](p)
            if sys.exc_info()[0] is not None:
                # an exception is propagating: write the partial trace
                # now (the normal-path finalize below is unreachable)
                _finish_trace()

        verdicts: dict[str, Verdict] = {}
        for i, (sc, tasks) in enumerate(plans):
            if cached[i] is not None:
                # cache hit: the whole scenario — verdict, diffs, metrics
                # (with their timestamp multisets), merged output image —
                # rehydrates from the store; replay never ran, so the
                # reported wall time is the metadata read (~0)
                ent = cached[i]
                verdict = Verdict(
                    scenario=sc.name, passed=ent.passed,
                    vacuous=ent.vacuous, diffs=ent.rebuild_diffs(),
                    metrics=ent.metrics, golden_path=sc.golden_bag_path,
                    cache="hit")
                image = ent.output_image
                n_in, n_out, n_drop = (ent.messages_in, ent.messages_out,
                                       ent.messages_dropped)
                n_parts, wall = ent.partitions, 0.0
            elif scn_error[i] is not None:
                # degraded: the scenario never produced comparable
                # outputs, so neither PASS nor FAIL is honest — an ERROR
                # verdict carries the cause lineage and an empty output
                # image, and is never banked in the result cache
                empty = Bag.open_write(backend="memory")
                empty.close()
                image = empty.chunked_file.image()
                verdict = Verdict(
                    scenario=sc.name, passed=False, error=scn_error[i],
                    golden_path=sc.golden_bag_path,
                    cache="miss" if cache is not None else None)
                n_in, n_out, n_drop = counts[i]
                n_parts = total_tasks[i]
                wall = (replay_end[i] - t0) if replay_end[i] else 0.0
            else:
                if tasks or needs[i]:
                    image, verdict = agg_out[i]
                else:
                    # pruned-empty scenario: a clean zero-message vacuous
                    # verdict, no tasks burned on the pool
                    merged, verdict = self.aggregator.aggregate(
                        sc.name, [], golden=sc.golden_bag_path,
                        messages_in=0)
                    image = merged.chunked_file.image()
                    merged.close()
                if cache is not None:
                    verdict.cache = "miss"
                n_in, n_out, n_drop = counts[i]
                n_parts = total_tasks[i]
                wall = (replay_end[i] - t0) if replay_end[i] else 0.0
            verdict.transport = scn_transport[i]
            report = SimulationReport(
                messages_in=n_in,
                messages_out=n_out,
                wall_time_s=wall,
                partitions=n_parts,
                scheduler_stats=stats,
                scenario=sc.name,
                backend=backend_name,
                batch_size=sc.batch_size,
                messages_dropped=n_drop,
                shards=len(sc.shard_paths),
                output_image=image,
                metrics=verdict.metrics,
            )
            verdict.report = report
            verdicts[sc.name] = verdict
            if (cache is not None and cache_keys[i] is not None
                    and cached[i] is None and scn_error[i] is None):
                # freshly computed + content-addressable: bank it (a
                # failed write costs coverage, never the suite)
                cache.put(cache_keys[i], _CachedResult(
                    scenario=sc.name, passed=verdict.passed,
                    vacuous=verdict.vacuous,
                    diffs=[{"topic": d.topic, "field": d.field,
                            "expected": d.expected, "actual": d.actual,
                            "detail": d.detail} for d in verdict.diffs],
                    metrics=verdict.metrics, output_image=image,
                    export_image=export_snaps.get(i),
                    messages_in=n_in, messages_out=n_out,
                    messages_dropped=n_drop, partitions=n_parts,
                    shards=len(sc.shard_paths), wall_time_s=wall))
        if cache is not None:
            self.last_cache_stats = dict(cache.stats)
        _finish_trace()
        if verdict_log is not None:
            self._persist_verdicts(verdict_log, manifest_path, verdicts,
                                   backend_name,
                                   stages=trace_out.get("stages"),
                                   metrics_snapshot=obs_metrics.snapshot())
        return verdicts

    @staticmethod
    def _persist_verdicts(verdict_log: str, manifest_path: Optional[str],
                          verdicts: dict[str, Verdict],
                          backend_name: str, *,
                          stages: Optional[dict] = None,
                          metrics_snapshot: Optional[dict] = None) -> None:
        """Append one JSONL record per scenario to ``verdict_log`` and
        rewrite the suite manifest (scenario → golden path → verdict).

        The log is append-only — consecutive suite runs accumulate a
        verdict history a CI job can diff or trend; the manifest
        (``manifest_path``, default ``<verdict_log>.manifest.json``) is
        the current snapshot a gate inspects without parsing history.
        Metric checksums ride along so a PASS can additionally be pinned
        bit-exactly across runs.  A traced run adds per-scenario
        ``stages`` (stage → busy ns, from the span timeline) to each
        record — what ``verdict_report`` trends — and every run embeds
        the ``repro.obs.metrics`` snapshot in the manifest.
        """
        now = time.time()
        records = []
        for name, v in verdicts.items():
            r = v.report
            rec = {
                "scenario": name,
                "status": v.status,
                "passed": v.passed,
                "vacuous": v.vacuous,
                "golden": v.golden_path,
                "diffs": [str(d) for d in v.diffs],
                "checksums": {t: m.checksum for t, m in v.metrics.items()},
                "messages_in": r.messages_in,
                "messages_out": r.messages_out,
                "messages_dropped": r.messages_dropped,
                "wall_time_s": r.wall_time_s,
                "partitions": r.partitions,
                "shards": r.shards,
                "backend": backend_name,
                "cache": v.cache,
                "transport": v.transport,
                "error": v.error,
                "unix_time": now,
            }
            if stages is not None:
                rec["stages"] = stages.get(name)
            records.append(rec)
        with open(verdict_log, "a") as f:
            for rec in records:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
        manifest = {
            "verdict_log": os.path.abspath(verdict_log),
            "backend": backend_name,
            "unix_time": now,
            "passed": all(r["passed"] for r in records),
            "scenarios": {
                r["scenario"]: {"golden": r["golden"],
                                "status": r["status"],
                                "passed": r["passed"],
                                "cache": r["cache"],
                                "transport": r["transport"]}
                for r in records
            },
        }
        if metrics_snapshot is not None:
            manifest["metrics"] = metrics_snapshot
        mpath = manifest_path or verdict_log + ".manifest.json"
        with open(mpath, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
            f.write("\n")


class DistributedSimulation:
    """Partition a recorded bag across a worker pool and replay it through
    user logic — the full platform of the paper, minus the physical cluster.

    Now a thin wrapper over a one-scenario :class:`ScenarioSuite`; prefer
    the suite API for anything beyond a single homogeneous replay.
    """

    def __init__(self, bag_path: str, user_logic: LogicRef,
                 num_workers: int = 4, num_partitions: Optional[int] = None,
                 use_memory_cache: bool = True,
                 latency_model_s: float = 0.0,
                 batch_size: Optional[int] = None,
                 backend: Union[str, ExecutorBackend] = "thread",
                 scheduler_kwargs: Optional[dict] = None):
        self.scenario = Scenario(
            name="sim", bag_path=bag_path, user_logic=user_logic,
            latency_model_s=latency_model_s, batch_size=batch_size,
            num_partitions=num_partitions or num_workers,
            use_memory_cache=use_memory_cache)
        self.num_workers = num_workers
        self.backend = backend
        self.scheduler_kwargs = scheduler_kwargs or {}

    @property
    def bag_path(self) -> str:
        return self.scenario.bag_path

    @property
    def user_logic(self) -> LogicRef:
        return self.scenario.user_logic

    def run(self, timeout: float = 300.0) -> SimulationReport:
        suite = ScenarioSuite([self.scenario], num_workers=self.num_workers,
                              backend=self.backend,
                              scheduler_kwargs=self.scheduler_kwargs)
        return suite.run(timeout=timeout)[self.scenario.name].report


def bag_to_partitions(bag_path: str, num_partitions: int,
                      topics: Optional[Sequence[str]] = None,
                      ) -> list[BinaryPartition]:
    """Export a bag as BinPipedRDD-style binary partitions (encode stage of
    Fig 4): each record becomes the uniform format [topic, timestamp, data].
    """
    bag = Bag.open_read(bag_path, backend="disk")
    parts = partition_bag(bag, num_partitions)
    out = []
    for lo, hi in parts:
        records = [encode([m.topic, m.timestamp, m.data])
                   for m in bag.read_messages(topics=topics,
                                              chunk_range=(lo, hi))]
        out.append(BinaryPartition(records,
                                   lineage=("bag", bag_path, lo, hi)))
    bag.close()
    return out
