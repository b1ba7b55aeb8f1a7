"""Low-overhead span tracer with per-thread lock-free ring buffers.

Design constraints (in priority order):

1. **Strictly no-op when disabled.**  Every instrumented seam is
   ``tr = trace.TRACER`` + an ``if tr is not None`` check — one global
   read, no call, no allocation.  This mirrors the proven
   ``chaos.active_plan()`` idiom.
2. **Allocation-light when enabled.**  Each thread owns a private ring
   of **preallocated slot lists**; :meth:`Tracer.begin` claims the next
   slot and mutates it in place, :meth:`Tracer.end` stamps ``t1``.  No
   locks on the hot path (the ring is single-writer by construction),
   no per-span object churn — the ring wraps, overwriting the oldest
   records (flight-recorder semantics, ``dropped`` counts the loss).
3. **One timeline across processes.**  ``perf_counter_ns`` is
   CLOCK_MONOTONIC on Linux — the same epoch for every process on the
   host — so driver and worker timestamps interleave directly.  Span
   ids embed ``(pid, buffer index, seq)`` and are unique host-wide;
   context is just the parent span id (an int), cheap to put in a task
   payload or an 8-byte wire frame annotation.

Record layout (one slot / one drained tuple)::

    (span_id, parent_id, name, cat, t0_ns, t1_ns, pid, tid, attrs)

``cat`` is the seam taxonomy used by ``repro.tools.trace_report``:
``sched`` / ``lane`` / ``play`` / ``logic`` / ``record`` /
``transport`` / ``shm`` / ``cache`` / ``agg`` / ``jit`` / ``suite``.

A device profile keeps its own clock.  :meth:`Tracer.anchor` ties the
two: it records ``perf_counter_ns`` inside a profiler annotation named
:data:`ANCHOR`, so the annotation's start in the profile minus the
``obs.anchor`` span's ``t0`` is the offset between the clocks.

Worker processes never export: :func:`task_begin` / :func:`task_end`
bracket one task, and ``task_end`` drains the local rings so the
records ride home on the existing result/spill path, where the driver
:meth:`Tracer.ingest`-s them into the suite timeline.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from time import perf_counter_ns
from typing import Iterable, List, Optional, Tuple

__all__ = [
    "ANCHOR", "TRACER", "SpanRecord", "Tracer", "disable", "enable",
    "enabled", "get_tracer", "ingest", "span", "task_begin", "task_end",
]

#: drained/normalised span tuple (see module docstring)
SpanRecord = Tuple[int, int, str, str, int, int, int, int, Optional[dict]]

# slot indices
_ID, _PARENT, _NAME, _CAT, _T0, _T1, _ATTRS = range(7)

#: per-thread ring capacity (slots); a slot is ~200 B of list + refs
DEFAULT_CAPACITY = 1 << 14

#: the device profile's host annotation that :meth:`Tracer.anchor` opens
ANCHOR = "repro.obs.anchor"


class _Buf:
    """One thread's private span ring (single writer, drained at
    quiescent points)."""

    __slots__ = ("pid", "tid", "slots", "cap", "pos", "seq", "prefix",
                 "dropped", "stack")

    def __init__(self, pid: int, tid: int, index: int, cap: int):
        self.pid = pid
        self.tid = tid
        self.cap = cap
        # preallocated, reused in place; t0 == 0 marks an empty slot
        self.slots = [[0, 0, "", "", 0, 0, None] for _ in range(cap)]
        self.pos = 0
        self.seq = 0
        # pid/buffer-index prefix keeps ids unique across the host
        self.prefix = (pid % 1_000_000) * 10**12 + index * 10**9
        self.dropped = 0
        self.stack: List[int] = []      # ambient context (span() only)


class Tracer:
    """Process-local span recorder; install via :func:`enable`.

    ``default_parent`` roots every span begun with ``parent=None`` and
    an empty ambient stack — helper threads (lane workers, net pumps)
    thus attach to the run root instead of orphaning.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 root_name: str = "trace", root_parent: int = 0):
        self.capacity = capacity
        self.pid = os.getpid()
        self._local = threading.local()
        self._bufs: List[_Buf] = []
        self._foreign: List[SpanRecord] = []    # ingested worker records
        self._lock = threading.Lock()
        self._worker = False        # True on executor-worker tracers
        # the root span: open from construction until drain_all()
        self._root_slot = self.begin(root_name, "suite", parent=root_parent)
        self.root_id = self._root_slot[_ID]
        self.default_parent = self.root_id

    # -- buffers -------------------------------------------------------------

    def _buf(self) -> _Buf:
        buf = getattr(self._local, "buf", None)
        if buf is None:
            with self._lock:
                buf = _Buf(self.pid, threading.get_ident(),
                           len(self._bufs), self.capacity)
                self._bufs.append(buf)
            self._local.buf = buf
        return buf

    # -- hot path ------------------------------------------------------------

    def begin(self, name: str, cat: str, parent: Optional[int] = None,
              attrs: Optional[dict] = None) -> list:
        """Open a span; returns the slot to pass to :meth:`end`."""
        buf = getattr(self._local, "buf", None)
        if buf is None:
            buf = self._buf()
        i = buf.pos
        buf.pos = 0 if i + 1 == buf.cap else i + 1
        slot = buf.slots[i]
        if slot[_T0] != 0:              # ring wrapped: oldest record lost
            buf.dropped += 1
        buf.seq += 1
        if parent is None:
            parent = buf.stack[-1] if buf.stack else self.default_parent
        slot[_ID] = buf.prefix + buf.seq
        slot[_PARENT] = parent
        slot[_NAME] = name
        slot[_CAT] = cat
        slot[_T1] = 0
        slot[_ATTRS] = attrs
        slot[_T0] = perf_counter_ns()
        return slot

    @staticmethod
    def end(slot: list) -> None:
        slot[_T1] = perf_counter_ns()

    @staticmethod
    def span_id(slot: list) -> int:
        return slot[_ID]

    @staticmethod
    def set_attrs(slot: list, attrs: Optional[dict]) -> None:
        """Attach/replace a span's attrs — for burst spans whose counts
        are only known at close."""
        slot[_ATTRS] = attrs

    def instant(self, name: str, cat: str, parent: Optional[int] = None,
                attrs: Optional[dict] = None) -> int:
        """A zero-duration marker span; returns its id."""
        slot = self.begin(name, cat, parent=parent, attrs=attrs)
        slot[_T1] = slot[_T0]
        return slot[_ID]

    def emit(self, name: str, cat: str, t0: int, t1: int,
             parent: Optional[int] = None,
             attrs: Optional[dict] = None) -> int:
        """Record an already-completed span with explicit timestamps —
        for seams that only know a span happened after the fact (e.g. a
        blocking recv that should not bill its idle wait).  Returns the
        span id."""
        slot = self.begin(name, cat, parent=parent, attrs=attrs)
        slot[_T0] = t0
        slot[_T1] = t1
        return slot[_ID]

    def anchor(self) -> int:
        """Tie this tracer's clock to a running device profile.

        Opens ``jax.profiler.TraceAnnotation(ANCHOR)``, reads
        ``perf_counter_ns`` inside it and records the reading as the
        instant span ``obs.anchor``; returns the reading.  The
        annotation's start in the profile minus the instant's ``t0`` is
        the offset from this clock to the profile's.  Whoever starts a
        device profile anchors right after starting it and right before
        stopping it: the two offsets bound the drift between the clocks.
        JAX is imported here, so processes that never anchor never load
        it."""
        import jax
        with jax.profiler.TraceAnnotation(ANCHOR):
            t = perf_counter_ns()
        self.emit("obs.anchor", "suite", t, t)
        return t

    # -- ambient context -----------------------------------------------------

    def ctx(self) -> int:
        """The current context span id — what to propagate into a task
        payload or a wire frame annotation."""
        buf = getattr(self._local, "buf", None)
        if buf is not None and buf.stack:
            return buf.stack[-1]
        return self.default_parent

    def push(self, span_id: int) -> None:
        self._buf().stack.append(span_id)

    def pop(self) -> None:
        buf = getattr(self._local, "buf", None)
        if buf is not None and buf.stack:
            buf.stack.pop()

    @contextmanager
    def span(self, name: str, cat: str = "suite",
             parent: Optional[int] = None, attrs: Optional[dict] = None):
        """Context manager for non-hot paths; nested spans on the same
        thread parent automatically."""
        slot = self.begin(name, cat, parent=parent, attrs=attrs)
        self.push(slot[_ID])
        try:
            yield slot
        finally:
            self.pop()
            self.end(slot)

    # -- collection ----------------------------------------------------------

    def ingest(self, records: Iterable[SpanRecord]) -> None:
        """Adopt records drained in another process (shipped back on the
        task result path) into this timeline."""
        with self._lock:
            self._foreign.extend(tuple(r) for r in records)

    def drain(self) -> List[SpanRecord]:
        """Collect and consume every finished (and still-open) record
        from this process's rings.  Call at quiescent points only —
        task end in a worker, suite end on the driver."""
        out: List[SpanRecord] = []
        with self._lock:
            bufs = list(self._bufs)
        for buf in bufs:
            pid, tid = buf.pid, buf.tid
            for slot in buf.slots:
                if slot[_T0] == 0:
                    continue
                if slot is self._root_slot and slot[_T1] == 0:
                    continue            # root stays open until drain_all
                out.append((slot[_ID], slot[_PARENT], slot[_NAME],
                            slot[_CAT], slot[_T0], slot[_T1], pid, tid,
                            slot[_ATTRS]))
                slot[_T0] = 0
                slot[_ATTRS] = None
        return out

    def drain_all(self) -> List[SpanRecord]:
        """Close the root span and return the full stitched timeline:
        local rings plus every ingested worker buffer."""
        if self._root_slot[_T1] == 0:
            self.end(self._root_slot)
        out = self.drain()
        with self._lock:
            out.extend(self._foreign)
            self._foreign = []
        return out

    @property
    def dropped(self) -> int:
        with self._lock:
            return sum(buf.dropped for buf in self._bufs)


#: the process-wide tracer; ``None`` = tracing disabled (the hot-path
#: check every seam performs).  Import the module, not the name:
#: ``from repro.obs import trace as otrace`` ... ``otrace.TRACER``.
TRACER: Optional[Tracer] = None

_install_lock = threading.Lock()


def enable(capacity: int = DEFAULT_CAPACITY, root_name: str = "trace",
           root_parent: int = 0) -> Tracer:
    """Install a fresh process-wide tracer (replacing any other)."""
    global TRACER
    with _install_lock:
        TRACER = Tracer(capacity=capacity, root_name=root_name,
                        root_parent=root_parent)
    return TRACER


def disable() -> None:
    global TRACER
    with _install_lock:
        TRACER = None


def enabled() -> bool:
    return TRACER is not None


def get_tracer() -> Optional[Tracer]:
    return TRACER


def ingest(records: Iterable[SpanRecord]) -> None:
    """Module-level convenience: adopt worker records if tracing is on."""
    tr = TRACER
    if tr is not None and records:
        tr.ingest(records)


@contextmanager
def span(name: str, cat: str = "suite", parent: Optional[int] = None,
         attrs: Optional[dict] = None):
    """No-op context manager when disabled; otherwise
    :meth:`Tracer.span`."""
    tr = TRACER
    if tr is None:
        yield None
        return
    with tr.span(name, cat, parent=parent, attrs=attrs) as slot:
        yield slot


# -- worker-side task bracket -------------------------------------------------

def task_begin(ctx: int, name: str = "task.run",
               attrs: Optional[dict] = None) -> Optional[list]:
    """Called by an executor worker when a payload carries trace context
    ``ctx`` (the driver-side dispatch span id).  In a thread-backend
    worker the driver tracer is already in place and the new span simply
    nests under ``ctx``.  In a process-backend worker (detected by a
    pid mismatch on the inherited tracer, or no tracer at all) a fresh
    worker tracer is installed, rooted at ``ctx``, so helper threads
    spawned during the task attach under it.
    """
    global TRACER
    tr = TRACER
    if tr is None or tr.pid != os.getpid():
        # worker tracer: no root span of its own — ctx is the root
        with _install_lock:
            tr = TRACER
            if tr is None or tr.pid != os.getpid():
                tr = Tracer.__new__(Tracer)
                tr.capacity = DEFAULT_CAPACITY
                tr.pid = os.getpid()
                tr._local = threading.local()
                tr._bufs = []
                tr._foreign = []
                tr._lock = threading.Lock()
                tr._worker = True
                tr._root_slot = [0, 0, "", "", 0, 0, None]
                tr.root_id = ctx
                tr.default_parent = ctx
                TRACER = tr
    if tr._worker:
        tr.default_parent = ctx     # one task at a time per worker
    slot = tr.begin(name, "sched", parent=ctx, attrs=attrs)
    tr.push(slot[_ID])
    return slot


def task_end(slot: Optional[list]) -> List[SpanRecord]:
    """Close the ``task.run`` span; in a process-backend worker, drain
    the local rings so the records ride back to the driver with the
    task result (a thread-backend worker's records are already in the
    driver tracer — nothing to ship)."""
    tr = TRACER
    if tr is None or slot is None:
        return []
    tr.pop()
    tr.end(slot)
    return tr.drain() if tr._worker else []
