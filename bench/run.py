"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up: make the cell's clips from the seed, build the perception step once
with weights from the seed (on the device, one jitted call), and run one
warm-up suite that compiles, or loads from the persistent compilation cache
(``.jax_cache`` at the checkout's root), every program the window runs; its outputs become the golden bags.  Then the
window: one client submits the suite, waits for every verdict and submits
it again, until a suite finishes at or after ``--seconds``.  With
``--trace 1`` the window's first suite is profiled, and the per-layer
metrics are read from it.  After the window the program's state is freed
and the plain reference checks a sample of what the window served
(``check.py``).

The last line of standard output is the result as one JSON object; the last
lines of standard error are the numbers compared, each with its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits 1
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: marker the profiled suite runs under, on the profiler's host timeline
SUITE_MARKER = "bench.suite"

#: JAX's event for every program compiled or loaded from the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts backend compilations while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def build_step(ref: str, weight_seed: int):
    """The perception step the suites resolve ``ref`` to, with weights from
    ``weight_seed``, built once before any worker thread asks for it."""
    import jax
    from repro import perception
    model = ref[len(perception.SCHEME):]
    perception._STEPS.pop(model, None)
    step = perception.PerceptionStep(model=model, seed=weight_seed)
    jax.block_until_ready(step.params)
    perception._STEPS[model] = step
    return perception.get_step(ref)


def free_program_state() -> None:
    """Drop the step and every device buffer, so the reference runs on an
    empty chip."""
    import jax
    from repro import perception
    perception._STEPS.clear()
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


class Profiler:
    """Profiles the window's first suite: the device trace, the program's
    spans and the step's call shapes."""

    def __init__(self, step, log_dir: str):
        self.step = step
        self.log_dir = log_dir
        self.calls: list = []
        self.spans: list = []
        self.t0_perf = 0

    def __call__(self, run_suite):
        import jax
        from repro.obs import trace as otrace
        orig = self.step.step_arrays

        def counted(batch):
            self.calls.append(tuple(batch["payload"].shape))
            return orig(batch)

        jax.profiler.start_trace(self.log_dir)
        tracer = otrace.enable(root_name="bench")
        self.step.step_arrays = counted
        try:
            with jax.profiler.TraceAnnotation(SUITE_MARKER):
                self.t0_perf = time.perf_counter_ns()
                run = run_suite()
        finally:
            del self.step.step_arrays
            otrace.disable()
            jax.profiler.stop_trace()
        t1 = time.perf_counter_ns()
        self.spans = [s for s in tracer.drain_all()
                      if s[4] >= self.t0_perf and 0 < s[5] <= t1]
        return run


def per_layer(cell, prof: Profiler, clips, peaks, compiles: int):
    """(metrics, device extras, breakdown) of the profiled suite."""
    import devtrace
    from harness import metric_reader
    trace = devtrace.load(devtrace.find_xplane(prof.log_dir))
    lo, hi = devtrace.marker(trace, SUITE_MARKER)
    r = devtrace.Readings(
        config=cell.config, flops=cell.flops, peaks=peaks,
        drive_s=sum(c.drive_s for c in clips), scenarios=len(clips),
        spans=prof.spans, step_calls=prof.calls, trace=trace, lo=lo, hi=hi,
        compiles=compiles)
    metrics = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"]).read(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    offset = lo - prof.t0_perf
    gaps = devtrace.label_gaps(devtrace.gaps(trace, lo, hi), prof.spans,
                               offset)
    breakdown = {
        "device_ops": devtrace.top(devtrace.self_times(
            devtrace.all_events(trace.ops), lo, hi).items()),
        "idle_gaps": devtrace.top(gaps),
    }
    device = {"busy_s": devtrace.busy_ns(trace, lo, hi) / 1e9,
              "window_s": (hi - lo) / 1e9}
    return metrics, device, breakdown


def end_to_end(cell, window_s: float, runs, clips, setup_s: float) -> dict:
    """``replay_rate``: drive-seconds of every scenario verdicted in the
    window over its length; ``setup_s``: process start to window start."""
    drive = sum(c.drive_s for c in clips) / len(clips)
    verdicted = sum(len(r.verdicts) for r in runs if r.verdicts is not None)
    values = {"replay_rate": drive * verdicted / window_s,
              "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks: dict,
             t_start: float, control=()) -> dict:
    """Set up, run the window, free the program, check: the result dict
    (with the compared values under ``values``)."""
    t_jax = time.perf_counter()
    import jax
    import check
    from harness import (Loop, count_outcomes, derived_seed, make_clips,
                         register_config)
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        ref = register_config(cell.config)
        t = time.perf_counter()
        clips = make_clips(cell.traffic, seed, tmp)
        t, t_clips = time.perf_counter(), time.perf_counter() - t
        wseed = derived_seed(seed, 1)
        step = build_step(ref, wseed)
        t, t_step = time.perf_counter(), time.perf_counter() - t
        loop = Loop(ref, clips, cell.traffic)
        loop.write_goldens(loop.run_suite(), tmp)
        log(f"set-up: jax {t_jax - t_start!r} s, clips {t_clips!r} s, "
            f"weights {t_step!r} s, warm-up suite "
            f"{time.perf_counter() - t!r} s")
        counter = CompileCounter()
        counter.armed = True
        prof = Profiler(step, os.path.join(tmp, "profile")) if trace else None
        del step
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        window_s, runs = loop.window(seconds, first=prof)
        counter.armed = False
        devices = jax.devices()[:cell.chips]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        log(f"window: {window_s!r} s, {len(runs)} suites "
            f"{[round(r.t_done - r.t_submit, 3) for r in runs]}, "
            f"setup {setup_s!r} s, compiles in window {counter.count}")
        log("scenario wall s: " + str(
            [[round(v.report.wall_time_s, 3) for v in r.verdicts.values()
              if v.report is not None]
             for r in runs if r.verdicts is not None]))
        free_program_state()
        attempted, failed = count_outcomes(runs, len(clips))
        values = check.compare(cell, wseed, seed, clips, runs, control)
        correct, shown = check.judge(values, check.limits(cell))
        dev = jax.devices()[0]
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": {},
                  "device": {"platform": dev.platform,
                             "kind": dev.device_kind,
                             "count": len(jax.devices()),
                             "memory_peak_bytes": int(peak)}}
        if trace:
            metrics, extra, breakdown = per_layer(cell, prof, clips, peaks,
                                                  counter.count)
            result["metrics"] = metrics
            result["device"].update(extra)
            result["breakdown"] = breakdown
        else:
            result["metrics"] = end_to_end(cell, window_s, runs, clips,
                                           setup_s)
        result["checks"] = shown
        result["values"] = values
        return result


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def find_chip(chips: int):
    """The device kind's peaks, or exit 1 naming what JAX found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: need {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s) "
                         f"({devs[0].device_kind})")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        kinds = json.load(f)["kinds"]
    if devs[0].device_kind not in kinds:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{devs[0].device_kind!r} in bench/peaks.json")
    return kinds[devs[0].device_kind]


def main(argv=None) -> int:
    args = parse(argv)
    # the compile cache stays inside the checkout, at a fixed path, whatever
    # the environment names; the program takes its directory from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import load_benchmark, resolve_cell
    cell = resolve_cell(load_benchmark(), args.workload)
    peaks = find_chip(cell.chips)
    import jax
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"bench: {cell.name} seed {args.seed} on {jax.devices()[0]}; "
        f"compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks, T_START)
    result.pop("values")            # leaves "checks" the line's last key
    import check
    check.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
