"""Tracing of the perception replay path: the spans a traced suite records
where the work happens (bag-cache fill, step, readback, partition close),
nothing recorded and the same outputs with tracing off, the named scopes
that reach the compiled step's op metadata, the clock anchor in a device
profile, and the compile listener behind the ``jit`` metrics scope."""

import glob
import json
import re

import numpy as np
import pytest

from repro.core import Bag, Scenario, ScenarioSuite
from repro.obs import export as oexport
from repro.obs import trace as otrace

TINY = "qwen3-4b-tiny"
N_MSGS, BATCH = 64, 16


def _bag(tmp_path, n=N_MSGS, payload=640):
    rng = np.random.default_rng(11)
    path = str(tmp_path / "sensors.bag")
    bag = Bag.open_write(path, chunk_bytes=4096)
    for i in range(n):
        bag.write("/lidar", 1_000_000 * i,
                  rng.integers(0, 256, payload, dtype=np.uint8).tobytes())
    bag.close()
    return path


def _cache_image_len(path):
    """Bytes of the in-memory bag cache a partition of the whole bag fills."""
    src = Bag.open_read(path, backend="disk")
    n = len(src.selection_image(chunk_range=(0, src.num_chunks)).image)
    src.close()
    return n


def _suite(path):
    return ScenarioSuite([Scenario("perc", path, "perception://" + TINY,
                                   batch_size=BATCH, num_partitions=1)],
                         num_workers=1)


def _records(trace_path):
    with open(trace_path) as f:
        return oexport.events_to_records(json.load(f)["traceEvents"])


def test_traced_suite_records_fill_readback_and_close(tmp_path):
    path = _bag(tmp_path)
    trace_path = str(tmp_path / "trace.json")
    v = _suite(path).run(timeout=300, trace=trace_path)["perc"]
    assert v.passed
    recs = _records(trace_path)
    by = {}
    for r in recs:
        by.setdefault(r[2], []).append(r)

    (fill,) = by["bag.cache_fill"]
    assert fill[3] == "play"
    # the whole bag is selected: every chunk is copied as bytes
    with Bag.open_read(path) as src:
        n_chunks = src.num_chunks
    assert fill[8] == {"messages": N_MSGS, "bytes": _cache_image_len(path),
                       "raw_chunks": n_chunks, "decoded_chunks": 0}

    steps, reads = by["logic.step"], by["perception.readback"]
    assert len(steps) == len(reads) == len(by["perception.step"]) \
        == N_MSGS // BATCH
    step_ids = {r[0]: r for r in steps}
    for rb in reads:
        outer = step_ids[rb[1]]             # nested in its logic.step
        assert outer[4] <= rb[4] <= rb[5] <= outer[5]
        assert rb[3] == "logic" and rb[8] == {"rows": BATCH}
    for ps in by["perception.step"]:
        assert ps[1] in step_ids
        assert ps[8] == {"rows": BATCH, "row_bytes": 640}

    assert "play.publish" not in by
    (close,) = by["partition.close"]
    assert close[3] == "record"
    assert max(r[5] for r in reads) <= close[4]
    # every stage the path bills is one of the taxonomy's
    stages = oexport.stage_breakdown(recs)["perc"]
    assert set(stages) <= set(oexport.STAGES) and "logic" in stages


def test_tracing_off_records_nothing_and_changes_no_output(tmp_path,
                                                           monkeypatch):
    path = _bag(tmp_path)
    traced = _suite(path).run(timeout=300,
                              trace=str(tmp_path / "t.json"))["perc"]
    begun = []
    monkeypatch.setattr(otrace.Tracer, "begin",
                        lambda *a, **kw: begun.append(a[1]))
    plain = _suite(path).run(timeout=300)["perc"]
    assert not otrace.enabled() and begun == []
    assert plain.passed and traced.passed
    assert plain.report.output_image == traced.report.output_image


def test_step_scopes_reach_the_compiled_ops():
    from repro.perception import SCHEME, resolve_config, step_hlo
    text = step_hlo(SCHEME + TINY, 4, 5 * resolve_config(TINY).d_model)
    dots = [re.search(r'op_name="([^"]*)"', line)
            for line in text.splitlines()
            if re.search(r"\s(dot|convolution)\(", line)]
    names = [m.group(1) for m in dots if m]
    for scope in ("attention", "mlp", "head"):
        assert any(f"/{scope}/" in n for n in names), (scope, names)


def test_step_hlo_is_the_program_the_resolved_step_runs():
    # built without weights, with the settings get_step's step has
    from repro.perception import (SCHEME, PerceptionStep, resolve_config,
                                  step_hlo)
    def instructions(text):
        # every instruction, less the id of the Python stack it came from
        return [re.sub(r"\s*stack_frame_id=\d+", "", line)
                for line in text.splitlines()
                if re.match(r"\s*(ROOT\s+)?%[\w.\-]+ = ", line)]

    nb = 3 * resolve_config(TINY).d_model
    assert PerceptionStep(TINY, seed=None).params is None
    got = instructions(step_hlo(SCHEME + TINY, 2, nb))
    assert got and got == instructions(PerceptionStep(TINY).hlo_text(2, nb))


def test_anchor_lands_in_the_device_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData

    tracer = otrace.Tracer(root_name="t")
    jax.profiler.start_trace(str(tmp_path))
    try:
        t = tracer.anchor()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
    hits = [e for plane in ProfileData.from_file(xplane).planes
            for line in plane.lines for e in line.events
            if e.name == otrace.ANCHOR]
    assert len(hits) == 1 and hits[0].duration_ns >= 0
    (inst,) = [s for s in tracer.drain_all() if s[2] == "obs.anchor"]
    assert inst[4] == inst[5] == t


def test_compile_listener_counts_and_spans_compiles():
    import jax
    import jax.numpy as jnp

    from repro.obs.compiles import watch_compiles
    scope = watch_compiles()
    assert watch_compiles() is scope            # one listener a process
    before = scope.snapshot()
    tracer = otrace.enable(root_name="t")
    try:
        # a shape no other test compiles
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(37)).block_until_ready()
    finally:
        otrace.disable()
    after = scope.snapshot()
    n = after["compiles"] - before.get("compiles", 0)
    assert n >= 1
    assert after["compile_ms"]["count"] - \
        (before.get("compile_ms") or {}).get("count", 0) == n
    spans = [s for s in tracer.drain_all() if s[2] == "jax.compile"]
    assert len(spans) == n
    assert all(s[3] == "jit" and 0 < s[4] < s[5] for s in spans)


@pytest.mark.parametrize("name,cat,stage", [
    ("perception.step", "logic", "logic"),
    ("perception.readback", "logic", "logic"),
    ("bag.cache_fill", "play", "read"),
    ("play.read", "play", "read"),
    ("partition.close", "record", "record"),
    ("jax.compile", "jit", None),
    ("task.run", "sched", None),
])
def test_stage_billing_of_the_replay_spans(name, cat, stage):
    ms = 1_000_000
    recs = [(1, 0, "sched.task", "sched", 1, 100 * ms, 0, 0,
             {"stage": ["scenario", "s"]}),
            (2, 1, name, cat, 10 * ms, 30 * ms, 0, 0, None)]
    got = oexport.stage_breakdown(recs).get("s", {})
    assert got == ({stage: 20 * ms} if stage else {})
