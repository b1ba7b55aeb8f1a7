"""Chrome/Perfetto trace export.

Emits the Trace Event JSON format (``{"traceEvents": [...]}``) that
https://ui.perfetto.dev and ``chrome://tracing`` load directly: one
complete (``"ph": "X"``) event per drained span, microsecond
timestamps, plus metadata events naming each process (driver vs
worker pids) and thread.  Span ids and parent ids ride in ``args`` so
the stitched parent/child structure survives the export — that is what
``repro.tools.trace_report`` and the stitching tests consume.
"""

from __future__ import annotations

import json
from typing import Iterable, List, Optional

from .trace import SpanRecord

__all__ = ["STAGES", "events_to_records", "stage_breakdown", "to_events",
           "write_trace"]

#: the pipeline-stage taxonomy ``stage_breakdown`` bills spans against
STAGES = ("read", "logic", "record", "transport", "cache", "aggregate")

_CAT_STAGE = {"play": "read", "logic": "logic", "record": "record",
              "transport": "transport", "shm": "transport", "cache": "cache",
              "agg": "aggregate"}


def _stage_of(name: str, cat: str, attrs: Optional[dict]) -> Optional[str]:
    """Map one span to the pipeline stage it bills.  ``sched`` / ``suite``
    spans are containers (queue wait + execution) and bill nothing, nor
    does ``jit`` (compiles)."""
    if cat == "lane":
        # lane spans bill the stage their consumer implements
        lane = str((attrs or {}).get("lane", ""))
        if lane.startswith("record"):
            return "record"
        if lane.startswith("bridge"):
            return "transport"
        if lane.startswith("metrics"):
            return "aggregate"
        return "logic"
    return _CAT_STAGE.get(cat)


def to_events(records: Iterable[SpanRecord],
              driver_pid: Optional[int] = None) -> List[dict]:
    """Convert drained span records to Chrome trace events."""
    events: List[dict] = []
    pids = {}
    for rec in records:
        try:
            span_id, parent, name, cat, t0, t1, pid, tid, attrs = rec
        except (TypeError, ValueError):
            continue                    # torn/foreign record: skip, don't die
        if not t0:
            continue
        args = {"id": span_id, "parent": parent}
        if attrs:
            args.update(attrs)
        if not t1:
            args["incomplete"] = True   # crash/drain caught the span open
            t1 = t0
        events.append({
            "name": name, "cat": cat or "span", "ph": "X",
            "ts": t0 / 1000.0, "dur": max(t1 - t0, 0) / 1000.0,
            "pid": pid, "tid": tid, "args": args,
        })
        pids.setdefault(pid, set()).add(tid)
    for pid, tids in sorted(pids.items()):
        role = "driver" if pid == driver_pid else "worker"
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": f"{role} {pid}"}})
        for tid in sorted(tids):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": f"thread-{tid}"}})
    return events


def stage_breakdown(records: Iterable[SpanRecord]) -> dict:
    """Per-scenario per-stage busy nanoseconds from drained records.

    Returns ``{scenario: {stage: ns}}``.  Scenario attribution walks each
    span's parent chain to the nearest ``sched.task`` span, whose
    ``stage`` attr carries the task lineage head (``["scenario", name]``
    or ``["aggregate", name]``); spans with no attributable ancestor land
    under ``"_suite"``.  A span whose *parent* already bills the same
    stage is skipped, so nesting (``logic.step`` inside the logic lane's
    ``lane.deliver``) never double-counts.
    """
    recs: dict = {}
    for rec in records:
        try:
            span_id, parent, name, cat, t0, t1, pid, tid, attrs = rec
        except (TypeError, ValueError):
            continue
        if not t0:
            continue
        recs[span_id] = (parent, name, cat, t0, t1, attrs)

    owner_memo: dict = {}

    def owner(sid: int) -> Optional[str]:
        chain = []
        cur, got = sid, None
        while cur and cur in recs:
            if cur in owner_memo:
                got = owner_memo[cur]
                break
            chain.append(cur)
            parent, name, _cat, _t0, _t1, attrs = recs[cur]
            stage = (attrs or {}).get("stage")
            if name == "sched.task" and stage:
                got = str(stage[1]) if len(stage) > 1 else None
                break
            cur = parent
        for s in chain:
            owner_memo[s] = got
        return got

    out: dict = {}
    for sid, (parent, name, cat, t0, t1, attrs) in recs.items():
        stage = _stage_of(name, cat, attrs)
        if stage is None:
            continue
        up = recs.get(parent)
        if up is not None and _stage_of(up[1], up[2], up[5]) == stage:
            continue                    # parent already bills this stage
        dur = max((t1 or t0) - t0, 0)
        scen = owner(sid) or "_suite"
        stages = out.setdefault(scen, {})
        stages[stage] = stages.get(stage, 0) + dur
    return out


def events_to_records(events: Iterable[dict]) -> List[SpanRecord]:
    """Rebuild span records from exported trace events — the inverse of
    :func:`to_events` (modulo µs→ns rounding), so ``trace_report`` and
    the stitching tests analyse a ``trace.json`` with the same helpers
    that analyse live drains."""
    out: List[SpanRecord] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = dict(e.get("args") or {})
        sid = args.pop("id", 0)
        parent = args.pop("parent", 0)
        incomplete = args.pop("incomplete", False)
        t0 = int(round(e.get("ts", 0.0) * 1000.0))
        t1 = 0 if incomplete else t0 + int(round(e.get("dur", 0.0) * 1000.0))
        out.append((sid, parent, e.get("name", ""), e.get("cat", ""),
                    t0, t1, e.get("pid", 0), e.get("tid", 0), args or None))
    return out


def write_trace(path, records: Iterable[SpanRecord],
                driver_pid: Optional[int] = None,
                metadata: Optional[dict] = None) -> int:
    """Write a Perfetto-loadable ``trace.json``; returns the number of
    span events written."""
    events = to_events(records, driver_pid=driver_pid)
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if metadata:
        doc["metadata"] = metadata
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, separators=(",", ":"))
    return sum(1 for e in events if e.get("ph") == "X")
