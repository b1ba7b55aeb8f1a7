"""Device time and idle time of a profiled suite, put on the program's
layers.

Two attributions, beside ``devtrace``'s reductions:

* idle time on host spans.  The program's ``repro.obs`` spans are on the
  host's ``perf_counter_ns`` clock; the device trace is on the profile's.
  :func:`clock_offset` maps one onto the other: from the program's clock
  anchor (``repro.obs.anchor`` in the profile, ``obs.anchor`` among the
  spans) where the run recorded one, else from the suite's first span,
  which opens a few milliseconds after the suite marker (5.8–8.7 ms in
  suites on a TPU v5e host, where both offsets gave the same readings:
  the spans it moves start and end in idle stretches).
  :func:`idle_under` then measures the idle time during which a chosen
  set of spans is open.
* device time on named scopes.  The model forward runs its sublayers
  under ``jax.named_scope`` (``attention``, ``mlp``, ``head``), and the
  scope reaches each op's ``op_name`` metadata in the compiled program.
  The trace names ops only by their HLO instruction, so
  :func:`step_program` has the program compile its step again for the
  shape the suite ran (``perception.step_hlo``) and reads the metadata
  from the HLO text; :func:`scope_share` sums the self time of the ops
  under a scope, where the trace shows that program: every op the step
  ran is one of its instructions, and every fusion it holds ran.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import Counter
from typing import NamedTuple

from devtrace import clip, gaps, self_times, union

#: the program's clock anchor: its profiler annotation and its span
ANCHOR_EVENT = "repro.obs.anchor"
ANCHOR_SPAN = "obs.anchor"

#: span categories that only contain other work: the suite root and the
#: scheduler's and executor's task wrappers
CONTAINERS = ("suite", "sched")

#: the jitted decode-and-forward program's name in the device trace
PROGRAM = "jit_step"


# -- idle time on host spans --------------------------------------------------

def anchor_offsets(r) -> list[int]:
    """trace − host offsets of every clock anchor the run recorded, in
    time order (empty where the profile or the spans lack them)."""
    host = sorted(s for n, s, _ in r.trace.host if n == ANCHOR_EVENT)
    spans = sorted(s[4] for s in r.spans if s[2] == ANCHOR_SPAN)
    if not host or len(host) != len(spans):
        return []
    return [h - s for h, s in zip(host, spans)]


def clock_offset(r):
    """Nanoseconds to add to a span's host time to put it on the trace's
    clock: the first anchor's offset, else the suite marker's start less
    the first span's; None for a run with no spans."""
    anchors = anchor_offsets(r)
    if anchors:
        return anchors[0]
    starts = [s[4] for s in r.spans if s[4] > 0]
    return r.lo - min(starts) if starts else None


def span_intervals(r, keep, offset: int) -> list[tuple[int, int]]:
    """Union of the spans ``keep(span)`` selects, on the trace's clock,
    cut to the suite."""
    return union(clip(((s[2], s[4] + offset, s[5] - s[4]) for s in r.spans
                       if s[5] > s[4] and keep(s)), r.lo, r.hi))


def overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(r, keep):
    """Percent of the suite in which no op runs and a span that ``keep``
    selects is open; None where no span is selected."""
    offset = clock_offset(r)
    if offset is None or r.hi <= r.lo or not r.trace.ops:
        return None
    iv = span_intervals(r, keep, offset)
    if not iv:
        return None
    return 100.0 * overlap_ns(gaps(r.trace, r.lo, r.hi), iv) / (r.hi - r.lo)


def idle_unattributed(r):
    """Percent of the suite in which no op runs and no span other than a
    container is open."""
    offset = clock_offset(r)
    if offset is None or r.hi <= r.lo or not r.trace.ops:
        return None
    idle = gaps(r.trace, r.lo, r.hi)
    covered = overlap_ns(idle, span_intervals(
        r, lambda s: s[3] not in CONTAINERS, offset))
    return 100.0 * (sum(b - a for a, b in idle) - covered) / (r.hi - r.lo)


# -- device time on named scopes ----------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S.*?\s([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")

#: instructions whose ``op_name`` names a fusion's scope: a fusion is
#: billed to its matrix multiply where it holds one (the elementwise root
#: of a fusion is often a residual add outside every scope)
HEAVY = ("dot", "convolution")


class StepProgram(NamedTuple):
    """What the trace is read against, from a compiled program's HLO text.

    ``scopes``: ``{instruction: op_name}`` of every instruction (``""``
    where it has none); a fusion takes the ``op_name`` of the first matrix
    multiply in the computation it calls, else its own.  ``fusions``: the
    fusions of the computations the device runs itself (the entry and the
    loop bodies, not those inside another fusion), each of which a run of
    the program executes."""
    scopes: dict
    fusions: frozenset


def hlo_program(text: str) -> StepProgram:
    """:class:`StepProgram` of an optimized HLO module's text."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    heavy: dict[str, str] = {}          # computation -> first dot's op_name
    fusions: dict[str, list] = {}       # computation -> its fusions
    comp = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        if op and opcode in HEAVY and comp is not None:
            heavy.setdefault(comp, op.group(1))
        c = _CALLS.search(line)
        if opcode == "fusion" and c:
            calls[name] = c.group(1)
            fusions.setdefault(comp, []).append(name)
    scopes = dict(own)
    for name, callee in calls.items():
        if callee in heavy:
            scopes[name] = heavy[callee]
    fused = set(calls.values())
    return StepProgram(scopes, frozenset(
        f for c, fs in fusions.items() if c not in fused for f in fs))


def in_scope(op_name: str, scope: str) -> bool:
    return f"/{scope}/" in op_name


_PROGRAMS: dict = {}


def step_program(r):
    """:class:`StepProgram` of the perception step the suite ran, compiled
    again from shapes, with the program's own settings, for its most
    common call shape; None where the suite made no step call or the
    program cannot give its step's HLO."""
    from repro import perception
    step_hlo = getattr(perception, "step_hlo", None)
    if not r.step_calls or step_hlo is None:
        return None
    rows, nbytes = Counter(r.step_calls).most_common(1)[0][0]
    key = (r.config["name"], rows, nbytes)
    if key not in _PROGRAMS:
        from harness import register_config
        _PROGRAMS[key] = hlo_program(
            step_hlo(register_config(r.config), rows, nbytes))
    return _PROGRAMS[key]


def step_op_times(r) -> dict[str, float]:
    """Self seconds per op of the step program in the suite: the ops that
    start inside one of its program events, on the same device."""
    out: dict[str, float] = {}
    for dev, evs in r.trace.ops.items():
        runs = union(clip((e for e in r.trace.modules.get(dev, ())
                           if e[0].startswith(PROGRAM)), r.lo, r.hi))
        starts = [a for a, _ in runs]

        def inside(e) -> bool:
            i = bisect_right(starts, e[1]) - 1
            return i >= 0 and e[1] < runs[i][1]

        for op, t in self_times(filter(inside, evs), r.lo, r.hi).items():
            out[op] = out.get(op, 0.0) + t
    return out


def scope_share(r, scope: str, program):
    """Percent of the step program's device time spent in ops under
    ``scope``; None where no op is under it, or where the trace is not of
    ``program``: an op the step ran that it does not hold, or a fusion of
    it that never ran."""
    if not program:
        return None
    step_t = r.device_s(r.trace.modules, lambda n: n.startswith(PROGRAM))
    if step_t <= 0:
        return None
    own = step_op_times(r)
    if (not own or any(op not in program.scopes for op in own)
            or not program.fusions <= own.keys()):
        return None
    t = sum(t for op, t in own.items()
            if in_scope(program.scopes[op], scope))
    return 100.0 * t / step_t if t > 0 else None
