"""Idle time put on host spans and device time put on named scopes
(``attribution.py`` and the readers over it), on hand-made traces with
known answers and on a small recorded chip trace."""

import os

import pytest
from conftest import DATA

import attribution
import devtrace
from harness import metric_reader

NEW = ("cache_fill_idle", "idle_unattributed", "head_share",
       "attention_share", "readback_ms")


def span(sid, name, cat, t0, t1, parent=0, tid=1, attrs=None):
    return (sid, parent, name, cat, t0, t1, 1, tid, attrs)


def readings(trace, spans, lo, hi, step_calls=(), drive_s=1.0):
    return devtrace.Readings(
        config={"name": "qwen3-4b", "out_features": 16}, flops=None,
        peaks={}, drive_s=drive_s, scenarios=1, spans=list(spans),
        step_calls=list(step_calls), trace=trace, lo=lo, hi=hi, compiles=0)


def idle_suite(host=()):
    # a suite from 1000 to 2000 on the trace clock; ops at 1300-1400 and
    # 1600-1700, so 800 ns of it idle
    return devtrace.Trace(
        ops={"/device:TPU:0": [("fusion.1", 1300, 100),
                               ("fusion.2", 1600, 100)]},
        modules={"/device:TPU:0": [("jit_step(1)", 1300, 100),
                                   ("jit_step(1)", 1600, 100)]},
        host=[("bench.suite", 1000, 1000), *host])


def test_overlapping_fills_on_two_threads_count_once():
    # host clock = trace clock - 900; the first span opens at the marker
    spans = [span(1, "sched.task", "sched", 100, 1100),
             span(2, "bag.cache_fill", "play", 150, 350, tid=1),
             span(3, "bag.cache_fill", "play", 250, 450, tid=2),
             # inside the busy op at 1300-1400: no idle under it
             span(4, "bag.cache_fill", "play", 420, 480, tid=3)]
    r = readings(idle_suite(), spans, 1000, 2000)
    assert attribution.clock_offset(r) == 900
    # fills cover 1050-1380 on the trace clock; idle there: 1050-1300
    assert metric_reader("cache_fill_idle").read(r) == pytest.approx(25.0)


def test_a_gap_under_containers_alone_is_unattributed():
    # host clock = trace clock - 900
    spans = [span(1, "sched.task", "sched", 100, 1100),
             span(2, "task.run", "sched", 110, 1090, parent=1),
             span(3, "bag.cache_fill", "play", 100, 400, parent=2),
             span(4, "play.read", "play", 500, 600, parent=2),
             span(5, "partition.close", "record", 800, 1100, parent=2)]
    r = readings(idle_suite(), spans, 1000, 2000)
    # idle 1000-1300, 1400-1600, 1700-2000; the spans cover all of it
    # but 1500-1600: 100 ns unattributed
    assert metric_reader("idle_unattributed").read(r) == pytest.approx(10.0)
    # with the spans that say what the task did left out, every idle
    # nanosecond is unattributed
    bare = readings(idle_suite(), spans[:2], 1000, 2000)
    assert metric_reader("idle_unattributed").read(bare) == \
        pytest.approx(80.0)
    assert metric_reader("device_idle").read(r) == pytest.approx(80.0)


def test_two_anchors_with_drift():
    # the anchors' annotations start 5000 and 4997 ns before their host
    # readings: the first sets the offset, the second bounds the drift
    host = [("repro.obs.anchor", 990, 2), ("repro.obs.anchor", 2003, 2)]
    spans = [span(1, "obs.anchor", "suite", 5990, 5990),
             span(2, "obs.anchor", "suite", 7000, 7000),
             span(3, "bag.cache_fill", "play", 6000, 6200)]
    r = readings(idle_suite(host), spans, 1000, 2000)
    assert attribution.anchor_offsets(r) == [-5000, -4997]
    assert attribution.clock_offset(r) == -5000
    # the fill maps to 1000-1200, all idle
    assert metric_reader("cache_fill_idle").read(r) == pytest.approx(20.0)
    # an anchor pair the profile lost is not used: the first span sets the
    # offset from the suite marker
    r2 = readings(idle_suite(host[:1]), spans, 1000, 2000)
    assert attribution.anchor_offsets(r2) == []
    assert attribution.clock_offset(r2) == 1000 - 5990


def test_readers_find_nothing_where_the_program_has_no_span():
    spans = [span(1, "sched.task", "sched", 100, 1100)]
    r = readings(idle_suite(), spans, 1000, 2000)
    assert metric_reader("cache_fill_idle").read(r) is None
    assert metric_reader("readback_ms").read(r) is None
    empty = readings(idle_suite(), [], 1000, 2000)
    assert metric_reader("idle_unattributed").read(empty) is None
    # no step call: no program to compile, no scope share
    assert metric_reader("head_share").read(empty) is None


def test_readback_per_drive_second():
    spans = [span(1, "perception.readback", "logic", 0, 3_000_000),
             span(2, "perception.readback", "logic", 10, 1_000_010, tid=2)]
    r = readings(idle_suite(), spans, 1000, 2000, drive_s=2.0)
    assert metric_reader("readback_ms").read(r) == pytest.approx(2.0)


HLO = """\
HloModule jit_step

%fused_computation.1 (p0: bf16[4,8], p1: bf16[8,8]) -> bf16[4,8] {
  %p0 = bf16[4,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  %convolution.3 = bf16[4,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/while/body/attention/bsh,hd->bsd/dot_general"}
  ROOT %add.1 = bf16[4,8]{1,0} add(%convolution.3, %p0), metadata={op_name="jit(step)/while/body/add"}
}

%body (p: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %fusion.145 = bf16[4,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/add"}
  %fusion.147 = bf16[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/while/body/mlp/mul"}
}

ENTRY %main.5 (x: bf16[4,8]) -> f32[4,1,16] {
  %while.13 = (s32[], bf16[4,8]) while(%t), condition=%cond, body=%body
  ROOT %fusion.82 = f32[4,1,16]{2,1,0} fusion(%y), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(step)/head/bsd,vd->bsv/dot_general"}
}
"""


def test_hlo_program_bills_a_fusion_to_its_matrix_multiply():
    prog = attribution.hlo_program(HLO)
    got = prog.scopes
    # the residual add at the root is outside every scope; the fusion's
    # dot is in attention
    assert attribution.in_scope(got["fusion.145"], "attention")
    assert attribution.in_scope(got["fusion.147"], "mlp")
    assert attribution.in_scope(got["fusion.82"], "head")
    assert got["while.13"] == ""        # no metadata, no scope
    assert not attribution.in_scope(got["add.1"], "attention")
    # the fusions the device runs: the loop body's and the entry's
    assert prog.fusions == {"fusion.145", "fusion.147", "fusion.82"}


def step_trace(ops, extra_ops=(), extra_modules=()):
    """A suite from 0 to 200 with one run of the step at 0-110, and other
    programs' ops and runs besides."""
    return devtrace.Trace(
        ops={"d": [*ops, *extra_ops]},
        modules={"d": [("jit_step(7)", 0, 110), *extra_modules]},
        host=[("bench.suite", 0, 200)])


STEP_OPS = [("while.13", 0, 100), ("fusion.145", 0, 40),
            ("fusion.147", 40, 50), ("fusion.82", 100, 10)]


def test_scope_share_of_the_step_program():
    prog = attribution.hlo_program(HLO)
    # another program's op, whose name the step does not hold, is no
    # part of the step
    tr = step_trace(STEP_OPS, [("copy.4", 150, 5)],
                    [("jit_convert_element_type(3)", 150, 5)])
    r = readings(tr, [], 0, 200, step_calls=[(4, 8)])
    assert attribution.scope_share(r, "attention", prog) == \
        pytest.approx(100 * 40 / 110)
    assert attribution.scope_share(r, "head", prog) == \
        pytest.approx(100 * 10 / 110)
    assert attribution.scope_share(r, "ssm", prog) is None


@pytest.mark.parametrize("case", ["unknown op", "fusion never ran"])
def test_scope_share_reads_nothing_from_another_program(case):
    prog = attribution.hlo_program(HLO)
    if case == "unknown op":
        # the step ran an op the compiled text does not hold
        prog = prog._replace(scopes={k: v for k, v in prog.scopes.items()
                                     if k != "while.13"})
        ops = STEP_OPS
    else:
        # a fusion of the compiled text never ran in the step
        ops = [e for e in STEP_OPS if e[0] != "fusion.147"]
    r = readings(step_trace(ops), [], 0, 200, step_calls=[(4, 8)])
    assert attribution.scope_share(r, "attention", prog) is None


def test_step_program_compiles_the_suites_step(monkeypatch):
    # the test config at test widths
    from harness import load_json
    cfg = load_json(os.path.join(DATA, "configs", "qwen3-small.json"))
    r = readings(idle_suite(), [], 1000, 2000,
                 step_calls=[(8, 640), (8, 640), (2, 640)])
    r.config = cfg
    monkeypatch.setattr(attribution, "_PROGRAMS", {})
    prog = attribution.step_program(r)
    assert list(attribution._PROGRAMS) == [("qwen3-small", 8, 640)]
    names = set(prog.scopes.values())
    for scope in ("attention", "mlp", "head"):
        assert any(attribution.in_scope(n, scope) for n in names), scope
    assert prog.fusions and prog.fusions <= prog.scopes.keys()


def test_step_program_of_a_program_without_step_hlo(monkeypatch):
    # a program older than ``perception.step_hlo``: nothing to read, and
    # the readers raise nothing
    from repro import perception
    monkeypatch.delattr(perception, "step_hlo")
    monkeypatch.setattr(attribution, "_PROGRAMS", {})
    r = readings(idle_suite(), [], 1000, 2000, step_calls=[(8, 640)])
    assert attribution.step_program(r) is None
    assert metric_reader("head_share").read(r) is None


def test_old_recorded_trace_reads_as_before():
    # one suite of 1 s camera and lidar clips profiled on a TPU v5e,
    # before the program had spans here, scopes or anchors
    tr = devtrace.load_json(os.path.join(
        DATA, "trace-qwen3-4b.cam_lidar.json.gz"))
    lo, hi = devtrace.marker(tr, "bench.suite")
    r = readings(tr, [], lo, hi)
    assert metric_reader("device_idle").read(r) == pytest.approx(
        100.0 * (1.0 - devtrace.busy_ns(tr, lo, hi) / (hi - lo)))
    for name in NEW:
        assert metric_reader(name).read(r) is None, name


def recorded():
    """One suite of four 1.6 s lidar clips (8 calls of 16 x 694,400 B)
    profiled on a TPU v5e with the program's spans, scopes and two clock
    anchors (one after the profile started, one before it stopped)."""
    import gzip
    import json
    path = os.path.join(DATA, "trace-qwen3-4b.lidar-scoped.json.gz")
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    tr = devtrace.from_json(d)
    lo, hi = devtrace.marker(tr, "bench.suite")
    spans = [tuple(s) for s in d["spans"]]
    return (readings(tr, spans, lo, hi, step_calls=map(tuple, d["step_calls"]),
                     drive_s=d["drive_s"]),
            attribution.StepProgram(d["scopes"], frozenset(d["fusions"])))


@pytest.mark.parametrize("name,value", [
    ("cache_fill_idle", 9.808), ("idle_unattributed", 3.739),
    ("head_share", 0.00886), ("attention_share", 41.57),
    ("readback_ms", 918.1)])
def test_recorded_chip_trace_with_scopes_and_anchors(monkeypatch, name,
                                                     value):
    r, prog = recorded()
    monkeypatch.setattr(attribution, "step_program", lambda r: prog)
    offsets = attribution.anchor_offsets(r)
    assert len(offsets) == 2 and abs(offsets[1] - offsets[0]) < 1000
    got = metric_reader(name).read(r)
    assert got == pytest.approx(value, rel=1e-3)
    # the suite's first span, opened 5.8 ms after the marker, in place of
    # the anchors: every idle stretch it moves stays idle, so the reading
    # holds
    bench_view = readings(
        devtrace.Trace(ops=r.trace.ops, modules=r.trace.modules,
                       host=[h for h in r.trace.host
                             if h[0].startswith("bench.")]),
        [s for s in r.spans if s[2] != "obs.anchor"], r.lo, r.hi,
        step_calls=r.step_calls, drive_s=r.drive_s)
    assert attribution.clock_offset(bench_view) - offsets[0] == \
        pytest.approx(-5.846e6, abs=1e4)
    assert metric_reader(name).read(bench_view) == pytest.approx(got)
