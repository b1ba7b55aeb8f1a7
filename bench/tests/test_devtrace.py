"""The trace reduction on a small hand-made trace and on a recorded one."""

import os

from conftest import DATA

import devtrace


def hand_made():
    # device ops on one chip, ns; a suite marker from 100 to 1100
    return devtrace.Trace(
        ops={"/device:TPU:0": [("fusion.1", 100, 200), ("_decode_kernel", 250,
                                                        100),
                               ("fusion.2", 600, 300)]},
        modules={"/device:TPU:0": [("jit_step(1)", 100, 350),
                                   ("jit_step(1)", 600, 300)]},
        host=[("bench.suite", 100, 1000)])


def test_busy_union_and_gaps():
    tr = hand_made()
    lo, hi = devtrace.marker(tr, "bench.suite")
    assert (lo, hi) == (100, 1100)
    # ops cover 100-350 and 600-900: 550 ns busy of 1000
    assert devtrace.busy_ns(tr, lo, hi) == 550
    assert devtrace.gaps(tr, lo, hi) == [(350, 600), (900, 1100)]


def test_time_by_name_clips_to_the_window():
    tr = hand_made()
    t = devtrace.time_by_name(devtrace.all_events(tr.ops), 0, 700)
    assert t == {"fusion.1": 200e-9, "_decode_kernel": 100e-9,
                 "fusion.2": 100e-9}


def test_self_times_take_nested_ops_out():
    tr = devtrace.Trace(ops={"d": [("while.1", 0, 100), ("fusion.1", 10, 30),
                                   ("fusion.2", 50, 20), ("copy.1", 120, 5)]})
    assert devtrace.self_times(devtrace.all_events(tr.ops), 0, 200) == {
        "while.1": 50e-9, "fusion.1": 30e-9, "fusion.2": 20e-9,
        "copy.1": 5e-9}
    assert devtrace.op_name("%fusion.150 = bf16[10,271]{1,0} fusion(%a)") \
        == "fusion.150"


def test_gaps_take_the_span_that_covers_most():
    # spans on the host clock; the trace clock is host + 1000
    spans = [(1, 0, "play.read", "play", -700, -350, 0, 0, None),
             (2, 0, "aggregate.merge", "agg", -150, 200, 0, 0, None),
             (3, 0, "sched.task", "sched", -1000, 200, 0, 0, None)]
    got = devtrace.label_gaps([(350, 600), (900, 1100)], spans, 1000)
    assert got == [("play.read", 250e-9), ("aggregate.merge", 200e-9)]


def test_recorded_chip_trace():
    # one suite of 1 s camera and lidar clips profiled on a TPU v5e
    tr = devtrace.load_json(os.path.join(
        DATA, "trace-qwen3-4b.cam_lidar.json.gz"))
    lo, hi = devtrace.marker(tr, "bench.suite")
    busy = devtrace.busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    # every op event nests in or follows another: self times add to busy
    own = devtrace.self_times(devtrace.all_events(tr.ops), lo, hi)
    assert abs(sum(own.values()) - busy / 1e9) < 1e-6
    kern = devtrace.time_by_name(devtrace.all_events(tr.ops), lo, hi,
                                 lambda n: n.split(".")[0] == "_sensor_decode")
    assert 0 < sum(kern.values()) < 0.01
    step = devtrace.time_by_name(devtrace.all_events(tr.modules), lo, hi,
                                 lambda n: n.startswith("jit_step"))
    assert 0 < sum(step.values()) <= (hi - lo) / 1e9
