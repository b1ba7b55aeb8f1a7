"""Reduction from a profiler trace and the program's spans to numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a plain
:class:`Trace`: device events (ops and whole programs) and host
annotations, in nanoseconds on the profile's own clock.  The functions below
reduce it: the busy union and idle share of a window, time by op or program
name, and the idle gaps labelled by the ``repro.obs`` span that was open on
the host meanwhile.  :func:`load_json` reads a trace kept as gzipped JSON
(``{"ops", "modules", "host"}`` of event lists), which is how the tests
check the reduction on a small recorded trace.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from dataclasses import dataclass, field

#: profile lines that hold device operations and whole device programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    """Events as (name, start ns, duration ns)."""
    ops: dict = field(default_factory=dict)        # device -> [event]
    modules: dict = field(default_factory=dict)    # device -> [event]
    host: list = field(default_factory=list)       # host annotations


def from_json(d: dict) -> Trace:
    return Trace(ops={k: [(op_name(e[0]),) + tuple(e[1:]) for e in v]
                      for k, v in d["ops"].items()},
                 modules={k: [tuple(e) for e in v]
                          for k, v in d["modules"].items()},
                 host=[tuple(e) for e in d["host"]])


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return from_json(json.load(f))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def op_name(name: str) -> str:
    """An op's short name: a device op event is named by its whole HLO
    instruction ("%fusion.150 = bf16[...] fusion(...)"); keep "fusion.150"."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str, host_prefix: str = "bench.") -> Trace:
    """Device planes' op and program lines, and the host annotations whose
    names start with ``host_prefix`` (the benchmark's own markers)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = tr.ops if line.name == OPS_LINE else tr.modules
                    dest.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events
                               if e.name.startswith(host_prefix))
    return tr


def marker(trace: Trace, name: str) -> tuple[int, int]:
    """(start, end) of the one host annotation called ``name``."""
    hits = [e for e in trace.host if e[0] == name]
    if len(hits) != 1:
        raise ValueError(f"want one host marker {name!r}, found {len(hits)}")
    return hits[0][1], hits[0][1] + hits[0][2]


def clip(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Event intervals cut to [lo, hi], empty ones dropped."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Nanoseconds in [lo, hi] during which an op ran, averaged over the
    devices that ran any."""
    per = [sum(b - a for a, b in union(clip(evs, lo, hi)))
           for evs in trace.ops.values()]
    per = [p for p in per if p > 0]
    return sum(per) / len(per) if per else 0.0


def gaps(trace: Trace, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of [lo, hi] on the first device that ran ops."""
    for evs in trace.ops.values():
        busy = union(clip(evs, lo, hi))
        if busy:
            out, t = [], lo
            for a, b in busy:
                if a > t:
                    out.append((t, a))
                t = max(t, b)
            if hi > t:
                out.append((t, hi))
            return out
    return []


def time_by_name(events, lo: int, hi: int, match=None) -> dict[str, float]:
    """Seconds per event name inside [lo, hi]; ``match(name)`` filters."""
    out: dict[str, float] = {}
    for name, s, d in events:
        if match is not None and not match(name):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def self_times(events, lo: int, hi: int) -> dict[str, float]:
    """Seconds per op name inside [lo, hi], each op less the ops nested in
    it (a loop's event spans the body's ops, which have events of their
    own)."""
    out: dict[str, float] = {}
    stack: list = []                    # [name, end, child ns]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, end, child, own = stack.pop()
            out[name] = out.get(name, 0.0) + (own - child) / 1e9
            if stack:
                stack[-1][2] += own

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        close(a)
        stack.append([name, b, 0, b - a])
    close(1 << 62)
    return out


def all_events(per_device: dict) -> list:
    return [e for evs in per_device.values() for e in evs]


def label_gaps(gap_list, spans, offset_ns: int) -> list[tuple[str, float]]:
    """Each gap with the name of the host span that covers most of it.

    ``spans`` are ``repro.obs`` records (name at index 2, t0/t1 at 4/5, on
    the host's ``perf_counter_ns`` clock); ``offset_ns`` maps that clock to
    the trace's (trace = perf + offset).  Among spans covering the same
    share, the shortest (the most specific) wins."""
    iv = [(s[4] + offset_ns, s[5] + offset_ns, s[2]) for s in spans
          if s[5] > s[4]]
    out = []
    for a, b in gap_list:
        best, best_key = "no span", (0, 0)
        for s, e, name in iv:
            cover = min(b, e) - max(a, s)
            if cover <= 0:
                continue
            key = (cover, -(e - s))
            if key > best_key:
                best, best_key = name, key
        out.append((best, (b - a) / 1e9))
    return out


def top(items, n: int = 10) -> list:
    """The ``n`` largest (name, seconds) pairs, largest first."""
    return [[k, v] for k, v in sorted(items, key=lambda kv: -kv[1])[:n]]


@dataclass
class Readings:
    """What the per-layer readers read: one profiled suite of the window.

    ``spans`` are the program's ``repro.obs`` records inside that suite;
    ``step_calls`` the (rows, bytes per row) of every perception step call
    in it, counted by the benchmark around the call; ``trace`` the device
    trace, with ``lo``/``hi`` the suite's bounds on its clock; ``compiles``
    the compilations counted after set-up, over the whole window."""
    config: dict
    flops: object
    peaks: dict
    drive_s: float
    scenarios: int
    spans: list
    step_calls: list
    trace: Trace
    lo: int
    hi: int
    compiles: int

    def span_s(self, name: str) -> float:
        return sum(s[5] - s[4] for s in self.spans
                   if s[2] == name and s[5] > s[4]) / 1e9

    def device_s(self, per_device: dict, match) -> float:
        return sum(time_by_name(all_events(per_device), self.lo, self.hi,
                                match).values())
