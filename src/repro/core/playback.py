"""Playback engine: the ROS side of the platform (paper §2, Fig 5).

ROS is "a message pool architecture: the sending node advertises to a Topic,
the receiving node subscribes to a Topic".  We reproduce those semantics —
ordering and timing, which is what simulation correctness depends on — with
an in-process bus rather than TCPROS (see DESIGN.md §8).

``RosPlay``   reads a Bag (disk- or memory-backed) and publishes its
              messages in timestamp order, optionally paced by wall clock.
              ``run_batched(n)`` delivers timestamp-ordered micro-batches
              through ``MessageBus.publish_batch`` so user logic can be a
              jitted array step instead of a per-message Python call.
              ``prefetch`` moves bag reading (chunk decode + time-order
              merge) onto a background reader thread.
``RosRecord`` subscribes to topics and writes everything to a Bag.

Together with :mod:`repro.core.bag`'s ``MemoryChunkedFile`` these are the two
"missing links" of §3.2: play-from-memory and record-to-memory.

Delivery modes
--------------

The bus delivers each subscription either **synchronously** (the seed
model: ``publish`` returns after every callback ran — deterministic, but a
slow subscriber stalls the publisher and the whole replay partition) or
**queued** (``subscribe(..., mode="queued", maxsize=N)``): the
subscription gets a bounded FIFO *lane* drained by a dedicated worker
thread.  Publishers enqueue and move on; a full lane blocks the publisher
(backpressure), so memory stays bounded and a hopelessly slow consumer
still paces the pipeline instead of being silently left behind.

Lane depth is fixed (``maxsize=N``), unbounded (``0``) or **adaptive**
(``None``): adaptive lanes observe the producer/consumer rate — every time
a producer finds the FIFO full the depth doubles, up to a memory cap —
so bursty sinks converge to a deeper lane while tight-memory workers keep
shallow ones.  Depth only moves *when* a publisher blocks, never delivery
order.

Determinism is preserved per lane: one worker thread drains one FIFO, so a
subscription sees exactly the synchronous delivery sequence, just later.
Subscriptions that must share one ordered stream (e.g. user logic attached
to several input topics, whose fault-injection RNG draws must happen in
publish order) pass the same ``group=`` name and share a single lane.
``drain()`` is the end-of-replay barrier: it blocks until every lane has
fully flushed — including work enqueued *by* queued callbacks into other
lanes — and re-raises the first callback error.  ``close()`` flushes and
stops the lane workers.  Callback exceptions never kill a lane worker
mid-replay; they are recorded and surface at the ``drain()`` barrier, like
the synchronous mode's immediate propagation but deferred to the join.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import defaultdict
from typing import Callable, Iterable, NamedTuple, Optional, Sequence

from repro import chaos
from repro.obs import metrics as obs_metrics
from repro.obs import trace as otrace

from .bag import Bag, Message, iter_time_ordered

Callback = Callable[[Message], None]
BatchCallback = Callable[[list[Message]], None]

#: per-message prefetch depth ``RosPlay.run(prefetch=True)`` defaults to
MESSAGE_PREFETCH = 256

#: messages per ``play.read`` trace span in per-message replay — spans are
#: chunked so tracing stays off the per-message hot path
TRACE_CHUNK = 256

# process-wide lane metrics (adaptive growth, producer stalls), folded
# into the repro.obs.metrics registry snapshot
_LANE_METRICS = obs_metrics.scope("lane")
_M_LANE_GROWN = _LANE_METRICS.counter("grown")
_M_LANE_STALLS = _LANE_METRICS.counter("enqueue_stalls")


class Publisher:
    def __init__(self, bus: "MessageBus", topic: str):
        self._bus = bus
        self.topic = topic

    def publish(self, timestamp: int, data: bytes) -> None:
        self._bus._dispatch(Message(self.topic, timestamp, data))

    def publish_message(self, msg: Message) -> None:
        if msg.topic != self.topic:
            raise ValueError(f"publisher for {self.topic}, got {msg.topic}")
        self._bus._dispatch(msg)


class _Lane:
    """One bounded-FIFO delivery lane drained by its own worker thread.

    Items are ``(callback, payload)`` pairs so several subscriptions (a
    ``group=``) can share the lane and keep their relative delivery order.
    ``put`` blocks while the queue is full — the bus's backpressure.
    Callback errors are recorded (never swallowed silently, never fatal to
    the worker; bounded — see ``MAX_ERRORS``) and re-raised at the
    ``drain()``/unsubscribe barrier.

    ``maxsize=None`` makes the lane **adaptive**: it starts at
    ``ADAPTIVE_START`` and doubles its depth every time a producer
    observes it full — a sink that keeps falling behind (bursty consumer,
    slow serializer) converges to a deeper lane instead of rate-limiting
    the publisher — bounded by ``ADAPTIVE_MAX`` items (the memory cap), at
    which point backpressure applies exactly as with a fixed depth.
    Adapting only ever changes *when* a publisher blocks, never FIFO
    delivery order, so results stay bit-identical.

    A publish racing lane shutdown (unsubscribe/close from another thread)
    must never silently lose a message: after the worker is gone, ``put``
    delivers inline, and both ``put`` and ``close`` sweep any straggler
    that slipped into the queue during the race window — the worst case is
    the old synchronous bus's (a late inline callback), not a drop.
    """

    #: deferred errors kept per lane; beyond this only a count is kept, so
    #: a subscriber failing on every message of a huge replay can't pin
    #: one traceback (and its message payload) per delivery until drain
    MAX_ERRORS = 8

    #: adaptive lanes start here (= the old fixed default) ...
    ADAPTIVE_START = 8
    #: ... and never grow beyond this many queued items ...
    ADAPTIVE_MAX = 1024
    #: ... nor past roughly this many queued payload *bytes* — the item
    #: cap alone would let MB-scale sensor messages balloon a lane, so
    #: deepening also respects the observed item size (largest payload
    #: seen; items whose size we can't read count as 0)
    ADAPTIVE_MAX_BYTES = 64 << 20

    __slots__ = ("key", "queue", "errors", "errors_dropped", "refs",
                 "closed", "adaptive", "grown", "_item_bytes", "_thread")

    def __init__(self, key: str, maxsize: Optional[int]):
        self.key = key
        self.adaptive = maxsize is None
        self.queue: "queue.Queue" = queue.Queue(
            maxsize=self.ADAPTIVE_START if self.adaptive else maxsize)
        self.errors: list[BaseException] = []
        self.errors_dropped = 0
        self.refs = 0                  # subscriptions sharing this lane
        self.closed = False
        self.grown = 0                 # adaptive depth doublings so far
        self._item_bytes = 0           # largest queued payload observed
        self._thread = threading.Thread(target=self._run,
                                        name=f"bus-lane-{key}", daemon=True)
        self._thread.start()

    @property
    def depth(self) -> int:
        """Current FIFO bound (0 = unbounded)."""
        return self.queue.maxsize

    @staticmethod
    def _payload_bytes(item) -> int:
        """Approximate payload size of one queued item (a Message or a
        micro-batch of them); 0 when unreadable."""
        data = getattr(item, "data", None)
        if data is not None:
            return len(data)
        if isinstance(item, (list, tuple)):
            return sum(len(getattr(m, "data", b"")) for m in item)
        return 0

    def _deepen(self, item) -> None:
        """Double an adaptive lane's depth (producer observed it full),
        capped at ``ADAPTIVE_MAX`` items *and* ``ADAPTIVE_MAX_BYTES`` of
        observed payload (largest item seen sizes the byte bound).
        Waiting producers are woken so they re-check the new bound."""
        self._item_bytes = max(self._item_bytes, self._payload_bytes(item))
        cap = self.ADAPTIVE_MAX
        if self._item_bytes:
            cap = min(cap, max(self.ADAPTIVE_START,
                               self.ADAPTIVE_MAX_BYTES // self._item_bytes))
        q = self.queue
        with q.mutex:
            if 0 < q.maxsize < cap:
                q.maxsize = min(q.maxsize * 2, cap)
                self.grown += 1
                _M_LANE_GROWN.inc()
                q.not_full.notify_all()

    def _record_error(self, e: BaseException) -> None:
        if len(self.errors) < self.MAX_ERRORS:
            self.errors.append(e)
        else:
            self.errors_dropped += 1

    def put(self, callback: Callable, item) -> None:
        if self.closed:
            # worker stopping/stopped: deliver inline with synchronous
            # semantics — errors propagate to the publisher, since this
            # lane may already be detached from the bus and its deferred
            # error list unread
            callback(item)
            return
        if self.adaptive and self.queue.full():
            # the producer is outrunning the sink: grow the window before
            # blocking (up to the caps; beyond them this is plain
            # backpressure)
            self._deepen(item)
        tr = otrace.TRACER
        if tr is not None and self.queue.full():
            # the producer is about to block — bill the stall to a span
            # (only probed under tracing: full() takes the queue mutex)
            _M_LANE_STALLS.inc()
            t0 = time.perf_counter_ns()
            self.queue.put((callback, item))    # blocks when full
            tr.emit("lane.enqueue_stall", "lane", t0, time.perf_counter_ns(),
                    attrs={"lane": self.key})
        else:
            self.queue.put((callback, item))    # blocks when full
        if self.closed and not self._thread.is_alive():
            # shutdown raced the enqueue and the worker is already gone —
            # sweep so the item is never stranded.  (While the worker is
            # still alive it either drains the item itself or close()'s
            # post-join sweep does; sweeping only after worker exit means
            # the stop sentinel can never be stolen from the worker.)
            self._sweep(record=False)

    def _run(self) -> None:
        # tracing is burst-granular: one ``lane.deliver`` span covers a
        # contiguous drain burst (first get after idle -> queue empty), so
        # the per-message cost is one global read + two cheap checks
        slot: Optional[list] = None
        n_burst = 0
        while True:
            callback, item = self.queue.get()
            tr = otrace.TRACER
            if tr is not None and slot is None and callback is not None:
                slot = tr.begin("lane.deliver", "lane")
                n_burst = 0
            try:
                if callback is None:            # stop sentinel
                    if slot is not None:
                        otrace.Tracer.set_attrs(
                            slot, {"lane": self.key, "n": n_burst})
                        otrace.Tracer.end(slot)
                    return
                plan = chaos.active_plan()
                if plan is not None:
                    fault = plan.probe("lane_stall", self.key)
                    if fault is not None:
                        # an injected slow consumer: delivery stalls, the
                        # lane backs up, publishers feel the backpressure
                        time.sleep(fault.param or 0.05)
                callback(item)
            except BaseException as e:          # noqa: BLE001 - defer to drain
                self._record_error(e)
            finally:
                self.queue.task_done()
            if slot is not None:
                n_burst += 1
                if self.queue.empty():
                    otrace.Tracer.set_attrs(
                        slot, {"lane": self.key, "n": n_burst})
                    otrace.Tracer.end(slot)
                    slot = None

    def _sweep(self, record: bool) -> None:
        """Deliver (inline) anything still queued after the worker exited.
        ``record=True`` defers callback errors to the lane's error list
        (shutdown paths that must not raise); ``record=False`` re-raises
        the first error to the sweeping publisher after finishing."""
        first: Optional[BaseException] = None
        while True:
            try:
                callback, item = self.queue.get_nowait()
            except queue.Empty:
                break
            try:
                if callback is not None:
                    callback(item)
            except BaseException as e:   # noqa: BLE001 - collect, finish
                if record:
                    self._record_error(e)
                elif first is None:
                    first = e
            finally:
                self.queue.task_done()   # keep flush()/idle bookkeeping sane
        if first is not None:
            raise first

    @property
    def idle(self) -> bool:
        return self.queue.unfinished_tasks == 0

    def flush(self) -> None:
        """Block until every item enqueued so far has been processed."""
        self.queue.join()

    def close(self) -> None:
        """Flush the backlog, then stop and join the worker; stragglers
        from a racing publish are delivered inline, never dropped."""
        if self.closed:
            return
        self.closed = True
        self.queue.put((None, None))
        self._thread.join()
        self._sweep(record=True)


class _Sub(NamedTuple):
    """One subscription entry: a callback, its delivery lane (``None`` lane
    = synchronous delivery), and an optional bus-side topic exclusion set
    (messages of excluded topics are skipped *before* any enqueue, so
    uninterested sinks cost the hot path nothing)."""
    callback: Callable
    lane: Optional[_Lane]
    exclude: Optional[frozenset] = None

    def wants(self, topic: str) -> bool:
        """The single exclusion predicate — every dispatch path (per-message
        and batched) must filter through this so the semantics can't
        diverge between publish shapes."""
        return self.exclude is None or topic not in self.exclude

    def deliver(self, item) -> None:
        if self.lane is None:
            self.callback(item)
        else:
            self.lane.put(self.callback, item)


class MessageBus:
    """Topic pub/sub message pool.  Thread-safe.  Synchronous subscriptions
    are delivered in publish order before ``publish`` returns (the seed
    contract); queued subscriptions decouple the subscriber onto its own
    bounded FIFO + worker thread — see the module docstring."""

    #: default bounded-FIFO depth for queued subscriptions
    DEFAULT_MAXSIZE = 8

    def __init__(self):
        self._subs: dict[str, list[_Sub]] = defaultdict(list)
        self._all: list[_Sub] = []
        self._batch_subs: dict[str, list[_Sub]] = defaultdict(list)
        self._batch_all: list[_Sub] = []
        self._lanes: dict[str, _Lane] = {}
        self._anon = itertools.count()
        self._lock = threading.Lock()
        self.published = 0

    def advertise(self, topic: str) -> Publisher:
        return Publisher(self, topic)

    # -- subscription management -------------------------------------------

    def _make_sub(self, callback: Callable, mode: str,
                  maxsize: Optional[int], group: Optional[str],
                  exclude_topics: Optional[Sequence[str]]) -> _Sub:
        """Build a subscription entry; caller holds ``self._lock``."""
        exclude = frozenset(exclude_topics) if exclude_topics else None
        if mode == "sync":
            return _Sub(callback, None, exclude)
        if mode != "queued":
            raise ValueError(f"unknown delivery mode {mode!r}")
        key = group if group is not None else f"anon-{next(self._anon)}"
        lane = self._lanes.get(key)
        if lane is None:
            lane = self._lanes[key] = _Lane(key, maxsize)
        lane.refs += 1
        return _Sub(callback, lane, exclude)

    @staticmethod
    def _check_duplicate(entries: list[_Sub], callback: Callable,
                         where: str) -> None:
        """Double-subscribing the same callback to the same topic is an
        error: ``unsubscribe`` removes exactly one registration, so a silent
        duplicate would leave a phantom subscription behind (the seed-era
        footgun) — fail at subscribe time instead."""
        if any(s.callback == callback for s in entries):
            raise ValueError(
                f"callback {callback!r} is already subscribed to {where}; "
                "double subscription would make unsubscribe ambiguous")

    def subscribe(self, topic: Optional[str], callback: Callback, *,
                  mode: str = "sync",
                  maxsize: Optional[int] = DEFAULT_MAXSIZE,
                  group: Optional[str] = None,
                  exclude_topics: Optional[Sequence[str]] = None) -> None:
        """``topic=None`` subscribes to every topic (rosbag record -a).

        ``mode="queued"`` hands the subscription a bounded FIFO
        (``maxsize``; 0 = unbounded; ``None`` = adaptive — the lane starts
        at ``_Lane.ADAPTIVE_START`` and deepens itself toward
        ``_Lane.ADAPTIVE_MAX`` while the producer outruns the sink)
        drained by a worker thread;
        subscriptions sharing a ``group`` name share one FIFO + worker, so
        their combined delivery order is the publish order.
        ``exclude_topics`` filters *at dispatch*: excluded messages are
        never delivered — and in queued mode never enqueued, keeping
        uninterested sinks (a recorder excluding replay inputs) entirely
        off the hot path and out of the backpressure budget."""
        with self._lock:
            entries = self._all if topic is None else self._subs[topic]
            self._check_duplicate(entries, callback,
                                  "all topics" if topic is None else topic)
            entries.append(self._make_sub(callback, mode, maxsize, group,
                                          exclude_topics))

    def unsubscribe(self, topic: Optional[str], callback: Callback) -> None:
        """Remove a subscription.  A queued subscription's lane is flushed
        first (pending deliveries complete — end-of-replay determinism) and
        its worker stopped once no other subscription shares it; deferred
        callback errors re-raise here."""
        self._remove(self._all if topic is None else self._subs[topic],
                     callback)

    def subscribe_batch(self, topic: Optional[str], callback: BatchCallback,
                        *, mode: str = "sync",
                        maxsize: Optional[int] = DEFAULT_MAXSIZE,
                        group: Optional[str] = None,
                        exclude_topics: Optional[Sequence[str]] = None,
                        ) -> None:
        """Batch subscription: receives ``list[Message]`` micro-batches from
        :meth:`publish_batch`.  Per-topic subscribers get the batch split by
        topic (uniform payload shape for array assembly); ``topic=None``
        receives the whole mixed-topic batch, minus any ``exclude_topics``
        (filtered at dispatch — an all-excluded batch is not delivered or
        enqueued at all).  ``mode="queued"`` enqueues whole micro-batches
        into the subscription's lane."""
        with self._lock:
            entries = (self._batch_all if topic is None
                       else self._batch_subs[topic])
            self._check_duplicate(
                entries, callback,
                "all topics (batch)" if topic is None else f"{topic} (batch)")
            entries.append(self._make_sub(callback, mode, maxsize, group,
                                          exclude_topics))

    def unsubscribe_batch(self, topic: Optional[str],
                          callback: BatchCallback) -> None:
        self._remove(self._batch_all if topic is None
                     else self._batch_subs[topic], callback)

    def _remove(self, entries: list[_Sub], callback: Callable) -> None:
        with self._lock:
            for i, s in enumerate(entries):
                if s.callback == callback:
                    del entries[i]
                    lane = s.lane
                    break
            else:
                raise ValueError(f"callback {callback!r} is not subscribed")
            if lane is not None:
                lane.refs -= 1
                if lane.refs > 0:
                    lane = None          # shared lane lives on
                else:
                    self._lanes.pop(lane.key, None)
        if lane is not None:
            lane.close()
            if lane.errors:
                raise lane.errors[0]

    # -- bridging (cross-process topic transport) ---------------------------

    def bridge(self, topics: "str | Sequence[str] | None", transport, *,
               batch: bool = False, maxsize: Optional[int] = None,
               group: Optional[str] = None) -> "BusBridge":
        """Forward ``topics`` (one topic, a sequence, or ``None`` for every
        topic) into a transport — the sending half of the distributed
        message pool (:mod:`repro.net`).

        The bridge is one queued subscription per topic sharing a single
        lane, whose callback is ``transport.send_message`` — so the remote
        end observes exactly this bus's publish order across all bridged
        topics, the transport's socket write runs on the lane worker (off
        the publish hot path), and a full lane or an exhausted credit
        window blocks the publisher: remote backpressure propagates to the
        local publisher through the standard lane mechanics.  ``maxsize``
        defaults to adaptive (``None``).

        ``transport`` is duck-typed (``send_message`` / ``send_batch`` /
        ``drain`` / ``close``) so the core layer never imports
        :mod:`repro.net`; pass a
        :class:`repro.net.transport.LaneTransport`.

        ``batch=True`` rides the batch subscription instead — one lane
        handoff and one ``send_batch`` per published micro-batch, the
        right shape for ``publish_batch`` buses (like ``RosRecord``'s
        ``batch`` flag, don't mix with per-message publishes of the same
        topics).  Note batch delivery is grouped per topic, so the remote
        end preserves per-topic order and batch order, not the exact
        cross-topic interleaving within one micro-batch — use the
        per-message bridge where that interleaving is contractual.

        Returns a :class:`BusBridge`: ``drain()`` is the cross-wire
        barrier, ``close()`` unsubscribes and releases the transport.
        Transport failures raise from the lane's deferred-error machinery
        — at :meth:`drain`/:meth:`BusBridge.close`/unsubscribe — never
        silently drop frames.
        """
        if isinstance(topics, str):
            topic_list: list[Optional[str]] = [topics]
        elif topics is None:
            topic_list = [None]
        else:
            topic_list = list(topics)
            if not topic_list:
                raise ValueError("bridge needs at least one topic")
        if group is None:
            group = f"bridge-{next(self._anon)}"
        callback = transport.send_batch if batch else transport.send_message
        sub = self.subscribe_batch if batch else self.subscribe
        for t in topic_list:
            sub(t, callback, mode="queued", maxsize=maxsize, group=group)
        return BusBridge(self, topic_list, transport, group, batch=batch)

    # -- barriers -----------------------------------------------------------

    def drain(self) -> None:
        """End-of-replay barrier: block until every queued lane is empty and
        idle — including deliveries enqueued *by* queued callbacks into
        other lanes while draining (a flush pass repeats until a pass finds
        everything already idle).  Re-raises the first deferred callback
        error.  A no-op on a bus with only synchronous subscriptions."""
        while True:
            with self._lock:
                lanes = list(self._lanes.values())
            if all(lane.idle for lane in lanes):
                break
            for lane in lanes:
                lane.flush()
        errors = [e for lane in lanes for e in lane.errors]
        if errors:
            raise errors[0]

    def close(self) -> None:
        """Flush and stop every queued lane worker and drop their
        subscriptions.  Never raises for deferred callback errors (shutdown
        path) — call :meth:`drain` first when errors must surface.  The bus
        stays usable for synchronous subscriptions afterwards."""
        with self._lock:
            lanes = list(self._lanes.values())
            self._lanes.clear()
            self._all = [s for s in self._all if s.lane is None]
            self._batch_all = [s for s in self._batch_all if s.lane is None]
            for reg in (self._subs, self._batch_subs):
                for topic in list(reg):
                    reg[topic] = [s for s in reg[topic] if s.lane is None]
        for lane in lanes:
            lane.close()

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self, msg: Message) -> None:
        with self._lock:
            subs = list(self._subs.get(msg.topic, ())) + list(self._all)
            self.published += 1
        for s in subs:
            if s.wants(msg.topic):
                s.deliver(msg)

    def publish_batch(self, messages: Sequence[Message]) -> int:
        """Deliver a micro-batch with one lock acquisition and one callback
        invocation (or lane enqueue) per batch subscriber — the bus half of
        the batched replay hot path.  Per-message subscribers still see
        every message individually, so recorders need no changes."""
        msgs = list(messages)
        if not msgs:
            return 0
        with self._lock:
            self.published += len(msgs)
            per_msg = {t: list(self._subs.get(t, ()))
                       for t in {m.topic for m in msgs}}
            all_subs = list(self._all)
            per_batch = {t: list(self._batch_subs.get(t, ()))
                         for t in {m.topic for m in msgs}}
            batch_all = list(self._batch_all)
        if all_subs or any(per_msg.values()):
            for m in msgs:
                for s in per_msg[m.topic]:
                    if s.wants(m.topic):
                        s.deliver(m)
                for s in all_subs:
                    if s.wants(m.topic):
                        s.deliver(m)
        if any(per_batch.values()):
            groups: dict[str, list[Message]] = defaultdict(list)
            for m in msgs:
                groups[m.topic].append(m)
            for t, group in groups.items():
                for s in per_batch[t]:
                    if s.wants(t):
                        s.deliver(group)
        for s in batch_all:
            if s.exclude is not None:
                kept = [m for m in msgs if s.wants(m.topic)]
                if kept:
                    s.deliver(kept)
            else:
                s.deliver(msgs)
        return len(msgs)


class BusBridge:
    """Handle for one :meth:`MessageBus.bridge` — the local face of a
    cross-process topic link.

    ``drain()`` is the end-to-end barrier: it flushes the bridge's lane
    (everything published so far has reached the transport) and then the
    transport itself (everything sent has been republished/committed on
    the remote end) — the cross-wire extension of ``MessageBus.drain``.
    ``close()`` unsubscribes, surfaces any deferred lane errors (transport
    send failures recorded mid-replay), and releases the transport.
    """

    def __init__(self, bus: "MessageBus", topics: Sequence[Optional[str]],
                 transport, group: str, batch: bool = False):
        self._bus = bus
        self._topics = list(topics)
        self._transport = transport
        self._group = group
        self._batch = batch
        self._open = True

    @property
    def transport(self):
        return self._transport

    def drain(self) -> None:
        with self._bus._lock:
            lane = self._bus._lanes.get(self._group)
        if lane is not None:
            lane.flush()
            if lane.errors:
                raise lane.errors[0]
        self._transport.drain()

    def close(self) -> None:
        """Unsubscribe and release the transport.  Deferred lane errors
        (a transport that died mid-replay) re-raise here — after every
        subscription is removed and the transport is closed, so a failed
        bridge never leaks a lane worker or a socket."""
        if not self._open:
            return
        self._open = False
        unsub = (self._bus.unsubscribe_batch if self._batch
                 else self._bus.unsubscribe)
        callback = (self._transport.send_batch if self._batch
                    else self._transport.send_message)
        errors: list[BaseException] = []
        for t in self._topics:
            try:
                unsub(t, callback)
            except ValueError:
                pass        # bus.close() already dropped the subscription
            except BaseException as e:  # noqa: BLE001 - collect, finish
                errors.append(e)
        try:
            self._transport.close()
        except BaseException as e:      # noqa: BLE001 - collect, finish
            errors.append(e)
        if errors:
            raise errors[0]

    def __enter__(self) -> "BusBridge":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class RosPlay:
    """Publish a bag's messages to the bus in global timestamp order.

    ``rate``: None = as fast as possible (simulation mode); otherwise a
    real-time factor (1.0 = original timing) — timing is derived from message
    timestamps like ``rosbag play``.
    """

    def __init__(self, bag: Bag, bus: MessageBus,
                 topics: Optional[Sequence[str]] = None,
                 rate: Optional[float] = None,
                 chunk_range: Optional[tuple[int, int]] = None,
                 start: Optional[int] = None,
                 end: Optional[int] = None):
        self._bag = bag
        self._bus = bus
        self._topics = topics
        self._rate = rate
        self._chunk_range = chunk_range
        self._start = start
        self._end = end
        self.messages_played = 0

    def _time_ordered(self) -> Iterable[Message]:
        """Bag chunks are time-ordered per-chunk but may interleave across
        topic boundaries; :func:`repro.core.bag.iter_time_ordered` merge-sorts
        them on a heap window and yields each message as soon as the bag
        index shows that no unread chunk holds an earlier one, so the
        first batch waits for its own chunks, not for the partition."""
        return iter_time_ordered(self._bag, topics=self._topics,
                                 chunk_range=self._chunk_range,
                                 start=self._start, end=self._end)

    def run(self, prefetch: int = 0) -> int:
        """Per-message replay.  ``prefetch > 0`` moves bag reading (chunk
        decode + heap-window ordering) onto a background reader thread
        buffering up to ``prefetch`` messages ahead of the publish loop —
        the read stage of the staged pipeline."""
        it: Iterable[Message] = self._time_ordered()
        if prefetch:
            from repro.data.pipeline import PrefetchIterator
            it = PrefetchIterator(iter(it), depth=prefetch)
        pubs: dict[str, Publisher] = {}
        t0_msg: Optional[int] = None
        t0_wall = time.monotonic()
        # tracing is chunk-granular: one ``play.read`` span per
        # TRACE_CHUNK messages covers read+decode+publish of the chunk
        tr = otrace.TRACER
        slot: Optional[list] = None
        chunk = 0
        try:
            for msg in it:
                if tr is not None and slot is None:
                    slot = tr.begin("play.read", "play")
                if self._rate is not None:
                    if t0_msg is None:
                        t0_msg = msg.timestamp
                    target = (msg.timestamp - t0_msg) / 1e9 / self._rate
                    delay = target - (time.monotonic() - t0_wall)
                    if delay > 0:
                        time.sleep(delay)
                pub = pubs.get(msg.topic)
                if pub is None:
                    pub = pubs[msg.topic] = self._bus.advertise(msg.topic)
                pub.publish_message(msg)
                self.messages_played += 1
                if slot is not None:
                    chunk += 1
                    if chunk >= TRACE_CHUNK:
                        otrace.Tracer.set_attrs(slot, {"n": chunk})
                        otrace.Tracer.end(slot)
                        slot = None
                        chunk = 0
        finally:
            if slot is not None:
                otrace.Tracer.set_attrs(slot, {"n": chunk})
                otrace.Tracer.end(slot)
            close = getattr(it, "close", None)
            if close is not None:       # stop an abandoned reader thread
                close()
        return self.messages_played

    def run_batched(self, batch_size: int, prefetch: int = 0) -> int:
        """Vectorized replay: publish timestamp-ordered micro-batches of up
        to ``batch_size`` messages via :meth:`MessageBus.publish_batch`.

        Wall-clock pacing (``rate``) applies at batch boundaries, keyed on
        the first timestamp of each batch — the array-step analogue of
        per-message pacing.  ``prefetch > 0`` double-buffers the framing:
        a background reader thread keeps up to ``prefetch`` micro-batches
        assembled ahead of the publish loop, so bag I/O overlaps the
        consumers (``prefetch=2`` is classic double buffering).
        """
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        from repro.data.pipeline import iter_message_batches
        t0_msg: Optional[int] = None
        t0_wall = time.monotonic()
        it = iter_message_batches(self._time_ordered(), batch_size,
                                  prefetch=prefetch)
        tr = otrace.TRACER
        try:
            it_ = iter(it)
            while True:
                # traced at batch granularity: ``play.read`` bills framing
                # (bag read + decode + heap ordering); what the publish
                # runs downstream bills its own spans
                if tr is not None:
                    r_slot = tr.begin("play.read", "play")
                    batch = next(it_, None)
                    otrace.Tracer.end(r_slot)
                else:
                    batch = next(it_, None)
                if batch is None:
                    break
                if self._rate is not None:
                    if t0_msg is None:
                        t0_msg = batch[0].timestamp
                    target = (batch[0].timestamp - t0_msg) / 1e9 / self._rate
                    delay = target - (time.monotonic() - t0_wall)
                    if delay > 0:
                        time.sleep(delay)
                self.messages_played += self._bus.publish_batch(batch)
        finally:
            close = getattr(it, "close", None)
            if close is not None:       # stop an abandoned reader thread
                close()
        return self.messages_played


class RosRecord:
    """Subscribe to topics and persist every message to a Bag.

    ``batch=True`` records through the batch subscription instead: one
    callback + one lock acquisition per micro-batch rather than per
    message, keeping the recorder off the per-message hot path of batched
    replay.  (Don't combine with per-message mode on the same bus — batched
    publishes would be recorded twice.)

    ``mode="queued"`` makes the recorder the sink stage of the staged
    pipeline: bag serialization runs on the recorder's own lane worker and
    overlaps replay/user logic instead of stalling them.  All of one
    recorder's subscriptions share a single lane (one writer thread), so
    the write order — and hence the recorded image — is exactly the
    synchronous one.  :meth:`stop` flushes the lane before unsubscribing,
    so every message published before ``stop()`` is in the bag when it
    returns.
    """

    def __init__(self, bus: MessageBus, bag: Bag,
                 topics: Optional[Sequence[str]] = None,
                 exclude_topics: Optional[Sequence[str]] = None,
                 batch: bool = False, mode: str = "sync",
                 queue_maxsize: Optional[int] = MessageBus.DEFAULT_MAXSIZE):
        self._bus = bus
        self._bag = bag
        self._topics = list(topics) if topics is not None else None
        self._exclude = set(exclude_topics or ())
        self._batch = batch
        self._mode = mode
        self._maxsize = queue_maxsize
        self._group = f"record-{id(self)}"
        self._cbs: list[tuple[Optional[str], Callback]] = []
        self._batch_cbs: list[tuple[Optional[str], BatchCallback]] = []
        self.messages_recorded = 0
        self._lock = threading.Lock()

    def start(self) -> None:
        # exclusion is enforced bus-side for the record-everything
        # subscription: excluded (replay input) traffic is never delivered
        # or enqueued, so it costs the hot path and the lane budget nothing;
        # the callback filter stays as backstop for per-topic subscriptions
        sub_kw = dict(mode=self._mode, maxsize=self._maxsize,
                      group=self._group)
        none_kw = dict(sub_kw, exclude_topics=self._exclude or None)
        if self._batch:
            def bcb(msgs: list[Message]) -> None:
                kept = [m for m in msgs if m.topic not in self._exclude]
                if not kept:
                    return
                tr = otrace.TRACER
                slot = (tr.begin("record.write", "record",
                                 attrs={"n": len(kept)})
                        if tr is not None else None)
                with self._lock:
                    for m in kept:
                        self._bag.write_message(m)
                    self.messages_recorded += len(kept)
                if slot is not None:
                    otrace.Tracer.end(slot)
            if self._topics is None:
                self._bus.subscribe_batch(None, bcb, **none_kw)
                self._batch_cbs.append((None, bcb))
            else:
                for t in self._topics:
                    self._bus.subscribe_batch(t, bcb, **sub_kw)
                    self._batch_cbs.append((t, bcb))
            return

        def cb(msg: Message) -> None:
            if msg.topic in self._exclude:
                return
            with self._lock:
                self._bag.write_message(msg)
                self.messages_recorded += 1
        if self._topics is None:
            self._bus.subscribe(None, cb, **none_kw)
            self._cbs.append((None, cb))
        else:
            for t in self._topics:
                self._bus.subscribe(t, cb, **sub_kw)
                self._cbs.append((t, cb))

    def stop(self) -> None:
        # bookkeeping first: a deferred lane error re-raised by unsubscribe
        # must not leave stale entries behind (a retried stop() would then
        # mask the real error with "not subscribed")
        cbs, self._cbs = self._cbs, []
        batch_cbs, self._batch_cbs = self._batch_cbs, []
        errors: list[BaseException] = []
        for t, cb in cbs:
            try:
                self._bus.unsubscribe(t, cb)
            except BaseException as e:      # noqa: BLE001 - collect, finish
                errors.append(e)
        for t, bcb in batch_cbs:
            try:
                self._bus.unsubscribe_batch(t, bcb)
            except BaseException as e:      # noqa: BLE001 - collect, finish
                errors.append(e)
        if errors:
            raise errors[0]

    def __enter__(self) -> "RosRecord":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
