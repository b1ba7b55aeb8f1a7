"""Observability layer tests: the span tracer (zero-cost when disabled,
ring-buffered when enabled), cross-process trace stitching through the
executor result path, the wire-frame context annotation, the metrics
registry (aggregation, reset-in-place, worker-delta absorption, the
deprecated counter shims), Perfetto export + stage breakdown, and the
``trace_report`` / ``verdict_report`` CLI faces.

User-logic functions are module-level so they cross the process-backend
pickle boundary.
"""

import json
import socket
import threading

import numpy as np
import pytest

from repro import chaos
from repro.core import Bag, Scenario, ScenarioSuite
from repro.obs import export as oexport
from repro.obs import metrics as ometrics
from repro.obs import trace as otrace

TOPICS = ("/camera", "/lidar")


def _make_bag(path, n=240, seed=0):
    b = Bag.open_write(path, chunk_bytes=4096)
    rng = np.random.RandomState(seed)
    for i in range(n):
        b.write(TOPICS[i % len(TOPICS)], i * 1000 + int(rng.randint(400)),
                bytes([i % 256]) * 48)
    b.close()
    return path


@pytest.fixture
def bag_path(tmp_path):
    return _make_bag(str(tmp_path / "drive.bag"))


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the tracer disabled — a leaked
    tracer would silently slow (and cross-contaminate) the session."""
    otrace.disable()
    yield
    otrace.disable()


def det_logic(msg):
    return ("/det" + msg.topic, msg.data[:4])


def prov_logic(msg):
    return ("/det" + msg.topic, msg.data[:4])


def cons_logic(msg):
    if msg.topic.startswith("/det"):
        return ("/seen" + msg.topic, msg.data[:2])
    return None


# -- tracer unit behaviour ----------------------------------------------------


def test_disabled_tracer_is_none_and_span_noops():
    assert otrace.TRACER is None and not otrace.enabled()
    with otrace.span("x", "suite") as slot:
        assert slot is None
    assert otrace.get_tracer() is None


def test_begin_end_drain_roundtrip():
    tr = otrace.enable(root_name="t")
    slot = tr.begin("work", "logic", attrs={"n": 3})
    tr.end(slot)
    records = tr.drain_all()
    names = {r[2] for r in records}
    assert names == {"t", "work"}
    work = next(r for r in records if r[2] == "work")
    sid, parent, name, cat, t0, t1, pid, tid, attrs = work
    assert parent == tr.root_id and cat == "logic"
    assert 0 < t0 <= t1 and attrs == {"n": 3}
    assert pid == tr.pid and tid == threading.get_ident()


def test_ambient_context_nests_and_ctx_propagates():
    tr = otrace.enable()
    with tr.span("outer", "suite") as outer:
        assert tr.ctx() == outer[0]
        with tr.span("inner", "suite") as inner:
            assert inner[1] == outer[0]     # parent = enclosing span
    assert tr.ctx() == tr.root_id           # stack unwound
    recs = {r[2]: r for r in tr.drain_all()}
    assert recs["inner"][1] == recs["outer"][0]
    assert recs["outer"][1] == tr.root_id


def test_ring_wrap_counts_drops_not_raises():
    tr = otrace.enable(capacity=8)
    for i in range(40):
        tr.instant(f"s{i}", "suite")
    assert tr.dropped >= 30
    records = tr.drain_all()
    assert 0 < len(records) <= 9            # ring + closed root


def test_span_ids_unique_across_threads():
    tr = otrace.enable()
    seen = []

    def work():
        for _ in range(50):
            seen.append(tr.instant("x", "suite"))

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(seen) == len(set(seen)) == 200


def test_task_bracket_thread_mode_keeps_driver_tracer():
    tr = otrace.enable()
    ctx = tr.instant("dispatch", "sched")
    slot = otrace.task_begin(ctx, attrs={"task": 1})
    assert otrace.TRACER is tr              # no replacement in-process
    shipped = otrace.task_end(slot)
    assert shipped == []                    # records stay local
    recs = {r[2]: r for r in tr.drain_all()}
    assert recs["task.run"][1] == ctx


def test_ingest_stitches_foreign_records():
    tr = otrace.enable()
    foreign = (999_000_001, tr.root_id, "task.run", "sched",
               100, 200, 4242, 1, None)
    otrace.ingest([foreign])
    records = tr.drain_all()
    assert foreign in records


# -- wire context annotation --------------------------------------------------


def test_frame_ctx_annotation_roundtrip():
    from repro.net.wire import T_DATA, FrameSocket
    a, b = socket.socketpair()
    fa, fb = FrameSocket(a), FrameSocket(b)
    try:
        fa.send_frame(T_DATA, b"payload", trace_ctx=123456789)
        ftype, body = fb.recv_frame()
        assert ftype == T_DATA and bytes(body) == b"payload"
        assert fb.last_trace_ctx == 123456789
        fa.send_frame(T_DATA, b"plain")
        ftype, body = fb.recv_frame()
        assert ftype == T_DATA and bytes(body) == b"plain"
        assert fb.last_trace_ctx is None    # annotation is per-frame
    finally:
        fa.close()
        fb.close()


# -- metrics registry ---------------------------------------------------------


def test_metric_primitives_and_reset():
    s = ometrics.Scope("t")
    c, g, h = s.counter("c"), s.gauge("g"), s.histogram("h")
    c.inc()
    c.inc(4)
    g.set(7)
    g.set(3)
    h.observe(10)
    h.observe(2)
    snap = s.snapshot()
    assert snap["c"] == 5
    assert snap["g"] == {"value": 3, "max": 7}
    assert snap["h"]["count"] == 2 and snap["h"]["mean"] == 6.0
    s.snapshot(reset=True)
    # reset happens IN PLACE: cached refs keep working afterwards
    c.inc()
    assert s.snapshot() == {"c": 1, "g": {"value": 0, "max": 0},
                            "h": {"count": 0, "total": 0, "min": None,
                                  "max": None, "mean": None}}


def test_registry_aggregates_same_named_scopes_and_absorbs():
    reg = ometrics.Registry()
    a, b = reg.scope("pool"), reg.scope("pool")
    a.counter("puts").inc(2)
    b.counter("puts").inc(3)
    reg.absorb({"pool": {"puts": 10}, "worker": {"steps": 1}})
    snap = reg.snapshot()
    assert snap["pool"]["puts"] == 15
    assert snap["worker"]["steps"] == 1


def test_registry_scopes_are_weak():
    reg = ometrics.Registry()
    s = reg.scope("gone")
    s.counter("x").inc()
    assert reg.snapshot()["gone"]["x"] == 1
    del s
    assert "gone" not in reg.snapshot()


def test_result_cache_counter_shims(tmp_path):
    from repro.cache import ResultCache
    cache = ResultCache(str(tmp_path / "store"))
    assert cache.load("0" * 64) is None
    assert (cache.hits, cache.misses) == (0, 1)
    assert cache.stats == {"hits": 0, "misses": 1, "puts": 0,
                           "put_errors": 0}


def test_scheduler_stats_is_registry_backed(bag_path):
    suite = ScenarioSuite([Scenario("s", bag_path, det_logic,
                                    num_partitions=2)], num_workers=2)
    v = suite.run(timeout=60)
    stats = v["s"].report.scheduler_stats
    assert stats["tasks_done"] >= 3         # 2 partitions + aggregate
    assert stats["retries"] == 0 and "spills" in stats


# -- export + stage breakdown -------------------------------------------------


def _rec(sid, parent, name, cat, t0, t1, pid=1, tid=1, attrs=None):
    return (sid, parent, name, cat, t0, t1, pid, tid, attrs)


def test_to_events_roundtrip_and_incomplete(tmp_path):
    records = [
        _rec(1, 0, "root", "suite", 1000, 9000),
        _rec(2, 1, "open", "lane", 2000, 0),        # never closed
    ]
    path = str(tmp_path / "trace.json")
    assert oexport.write_trace(path, records, driver_pid=1) == 2
    doc = json.load(open(path))
    events = doc["traceEvents"]
    x = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"root", "open"}
    assert [e for e in x if e["name"] == "open"][0]["args"]["incomplete"]
    back = oexport.events_to_records(events)
    assert sorted(r[0] for r in back) == [1, 2]
    assert {r[2]: r[1] for r in back} == {"root": 0, "open": 1}


def test_stage_breakdown_attribution_and_dedup():
    ms = 1_000_000
    records = [
        _rec(1, 0, "suite.run", "suite", 1, 100 * ms),
        _rec(2, 1, "sched.task", "sched", 1, 90 * ms,
             attrs={"stage": ["scenario", "s1"]}),
        _rec(3, 2, "play.read", "play", 1, 10 * ms + 1),
        # the logic lane's burst span ...
        _rec(4, 2, "lane.deliver", "lane", 10 * ms, 50 * ms,
             attrs={"lane": "logic"}),
        # ... encloses chunked logic spans: only the lane bills "logic"
        _rec(5, 4, "logic.step", "logic", 11 * ms, 49 * ms),
        _rec(6, 2, "lane.deliver", "lane", 10 * ms, 30 * ms,
             attrs={"lane": "record-1"}),
        # suite-level span with no sched.task ancestor
        _rec(7, 1, "cache.load", "cache", 1, 5 * ms + 1),
        # the jitted decode+forward's host side bills logic
        _rec(8, 2, "perception.step", "logic", 50 * ms, 70 * ms),
        # the bag-cache fill bills read
        _rec(10, 2, "bag.cache_fill", "play", 80 * ms, 85 * ms),
    ]
    bd = oexport.stage_breakdown(records)
    assert bd["s1"] == {"read": 15 * ms, "logic": 60 * ms,
                        "record": 20 * ms}
    assert bd["_suite"] == {"cache": 5 * ms}


# -- end-to-end: traced suite runs -------------------------------------------


def _ids_and_parents(events):
    x = [e for e in events if e.get("ph") == "X"]
    ids = {e["args"]["id"] for e in x}
    return x, ids


def test_traced_thread_suite_single_rooted_timeline(bag_path, tmp_path):
    trace_path = str(tmp_path / "trace.json")
    suite = ScenarioSuite(
        [Scenario("s1", bag_path, det_logic, num_partitions=2),
         Scenario("piped", bag_path, det_logic, pipeline=True,
                  latency_model_s=0.0001)],
        num_workers=2)
    verdicts = suite.run(timeout=120, trace=trace_path)
    assert all(v.passed for v in verdicts.values())
    assert not otrace.enabled()             # run() tears its tracer down

    events = json.load(open(trace_path))["traceEvents"]
    x, ids = _ids_and_parents(events)
    assert len(x) > 10
    by_id = {e["args"]["id"]: e for e in x}
    roots = [e for e in x if e["args"]["parent"] == 0]
    assert len(roots) == 1                  # single rooted timeline
    for e in x:                             # every span reaches the root
        cur, hops = e, 0
        while cur["args"]["parent"] != 0:
            assert cur["args"]["parent"] in ids, \
                f"orphan span {cur['name']}"
            cur = by_id[cur["args"]["parent"]]
            hops += 1
            assert hops < 50
    cats = {e["cat"] for e in x}
    assert {"suite", "sched", "play", "logic", "lane"} <= cats


def test_traced_run_is_bit_identical(bag_path, tmp_path):
    def sums(**kw):
        v = ScenarioSuite([Scenario("s", bag_path, det_logic,
                                    num_partitions=2)],
                          num_workers=2).run(timeout=60, **kw)
        return {t: m.checksum for t, m in v["s"].metrics.items()}

    assert sums() == sums(trace=str(tmp_path / "t.json"))


def test_traced_process_suite_stitches_worker_spans(bags_pair, tmp_path):
    """The acceptance shape: process backend + wire export + cache, one
    trace covering scheduler/lane/transport/cache/logic/play seams, every
    worker-side span stitched under a driver-side parent."""
    import os
    trace_path = str(tmp_path / "trace.json")
    suite = ScenarioSuite(
        [Scenario("prov", bags_pair[0], "tests.test_obs:prov_logic",
                  exports=("/det/camera", "/det/lidar")),
         Scenario("cons", bags_pair[1], "tests.test_obs:cons_logic",
                  imports=("/det/camera", "/det/lidar"))],
        num_workers=2, backend="process", export_transport="wire")
    verdicts = suite.run(timeout=180, trace=trace_path,
                         cache=str(tmp_path / "cache"))
    assert all(v.passed for v in verdicts.values())

    events = json.load(open(trace_path))["traceEvents"]
    x, ids = _ids_and_parents(events)
    by_id = {e["args"]["id"]: e for e in x}
    driver_pid = os.getpid()
    worker = [e for e in x if e["pid"] != driver_pid]
    assert worker, "no worker-side spans shipped home"
    for e in worker:                        # driver-side ancestor exists
        cur, hops = e, 0
        while cur["pid"] != driver_pid:
            parent = cur["args"]["parent"]
            assert parent in ids, f"orphan worker span {cur['name']}"
            cur = by_id[parent]
            hops += 1
            assert hops < 50
    for e in x:                             # and no orphans anywhere
        assert e["args"]["parent"] == 0 or e["args"]["parent"] in ids
    cats = {e["cat"] for e in x}
    assert {"suite", "sched", "play", "logic", "lane", "transport",
            "cache"} <= cats

    # warm re-run: hits rehydrate, trace still written and parseable
    verdicts2 = ScenarioSuite(
        [Scenario("prov", bags_pair[0], "tests.test_obs:prov_logic",
                  exports=("/det/camera", "/det/lidar")),
         Scenario("cons", bags_pair[1], "tests.test_obs:cons_logic",
                  imports=("/det/camera", "/det/lidar"))],
        num_workers=2, backend="process",
        export_transport="wire").run(timeout=180, trace=trace_path,
                                     cache=str(tmp_path / "cache"))
    assert {v.cache for v in verdicts2.values()} == {"hit"}
    cats2 = {e["cat"]
             for e in json.load(open(trace_path))["traceEvents"]
             if e.get("ph") == "X"}
    assert "cache" in cats2


@pytest.fixture
def bags_pair(tmp_path):
    return (_make_bag(str(tmp_path / "a.bag"), seed=1),
            _make_bag(str(tmp_path / "b.bag"), seed=2))


def test_worker_crash_leaves_parseable_partial_trace(bag_path, tmp_path):
    trace_path = str(tmp_path / "trace.json")
    chaos.install(chaos.ChaosPlan(
        [chaos.Fault("worker_crash", target="w0", count=1)], seed=3))
    try:
        suite = ScenarioSuite(
            [Scenario("s", bag_path, "tests.test_obs:det_logic",
                      num_partitions=3)],
            num_workers=2, backend="process",
            scheduler_kwargs={"max_attempts": 3,
                              "heartbeat_timeout": 0.3})
        verdicts = suite.run(timeout=120, trace=trace_path)
        assert verdicts["s"].passed
    finally:
        chaos.uninstall()
    events = json.load(open(trace_path))["traceEvents"]
    x, ids = _ids_and_parents(events)
    assert x                                # partial trace, never empty
    for e in x:                             # crash loses spans, not links
        assert e["args"]["parent"] == 0 or e["args"]["parent"] in ids
    assert any(e["name"] == "sched.worker_death" for e in x)


def test_crash_mid_suite_still_writes_flight_recording(bag_path, tmp_path):
    trace_path = str(tmp_path / "trace.json")

    def boom(msg):
        raise RuntimeError("logic exploded")

    suite = ScenarioSuite(
        [Scenario("s", bag_path, boom, num_partitions=2)],
        num_workers=2, scheduler_kwargs={"max_attempts": 2})
    with pytest.raises(RuntimeError):
        suite.run(timeout=60, trace=trace_path)
    assert not otrace.enabled()
    events = json.load(open(trace_path))["traceEvents"]
    assert any(e.get("ph") == "X" for e in events)
    assert any(e.get("name") == "sched.retry" for e in events)


# -- CLI faces ---------------------------------------------------------------


def test_trace_report_cli(bag_path, tmp_path, capsys):
    from repro.tools import trace_report
    trace_path = str(tmp_path / "trace.json")
    ScenarioSuite([Scenario("s1", bag_path, det_logic,
                            num_partitions=2)],
                  num_workers=2).run(timeout=60, trace=trace_path)
    out_json = str(tmp_path / "report.json")
    assert trace_report.main([trace_path, "--strict",
                              "--json", out_json]) == 0
    printed = capsys.readouterr().out
    assert "spans across" in printed and "s1" in printed
    report = json.load(open(out_json))
    assert report["spans"] > 0 and not report["orphans"]
    assert "s1" in report["scenarios"]

    empty = str(tmp_path / "empty.json")
    json.dump({"traceEvents": []}, open(empty, "w"))
    assert trace_report.main([empty, "--strict"]) == 1
    capsys.readouterr()


def test_verdict_report_stage_trending_and_metrics(tmp_path, capsys):
    from repro.tools import verdict_report
    base = {"status": "PASS", "passed": True, "vacuous": False,
            "checksums": {}, "cache": None}
    runs = [dict(base, scenario="s", wall_time_s=1.0, unix_time=i,
                 stages={"read": 100_000_000, "logic": 1_000_000_000})
            for i in range(3)]
    # wall flat, but the logic stage tripled — must still flag
    runs.append(dict(base, scenario="s", wall_time_s=1.0, unix_time=3,
                     stages={"read": 100_000_000,
                             "logic": 3_000_000_000}))
    log = str(tmp_path / "v.jsonl")
    with open(log, "w") as f:
        for r in runs:
            f.write(json.dumps(r) + "\n")
    manifest = {"metrics": {"scheduler": {"tasks_done": 7},
                            "cache": {"hits": 2,
                                      "depth": {"value": 3, "max": 9}}}}
    mpath = log + ".manifest.json"
    json.dump(manifest, open(mpath, "w"))

    rc = verdict_report.main([log, "--metrics", "--strict"])
    printed = capsys.readouterr().out
    assert rc == 1
    assert "stage logic" in printed
    assert "stage read" not in printed      # the flat stage stays quiet
    assert "tasks_done=7" in printed and "depth=3" in printed
