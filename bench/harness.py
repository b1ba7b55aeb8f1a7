"""The benchmark's own machinery: the layout it reads, the traffic it makes,
and the closed loop it drives.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration as it is run (its
  published keys, the program's ``ModelConfig`` fields under ``model``, and
  the family that names ``bench/reference/<family>.py`` and
  ``bench/flops/<family>.py``);
* ``bench/traffic/<traffic>.json``: a traffic mix, read by the one
  generator below;
* ``bench/cells/<workload>.json``: the limits the correctness check holds
  the cell to;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

The loop is one client: it submits a suite of scenarios through
``ScenarioSuite(..., backend="thread")``, waits for every verdict, and
submits the next.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: the scheduler's heartbeat window, in seconds.  On the thread backend a
#: worker that crashes or is killed reads as dead at once (``worker_alive``);
#: the heartbeat sweep only adds a false loss of every worker when the whole
#: process stands still for longer than the window, which a host that shares
#: its cores does for seconds at a time, past the 2 s default.
HEARTBEAT_S = 30.0

# -- layout -------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(package: str, name: str):
    """``bench/<package>/<name>.py``, imported by name.  A name that has no
    file is a FileNotFoundError, not a silent default."""
    if importlib.util.find_spec(f"{package}.{name}") is None:
        raise FileNotFoundError(
            os.path.join(BENCH_DIR, package, name + ".py"))
    return importlib.import_module(f"{package}.{name}")


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    reference: object = None
    flops: object = None

    @property
    def family(self) -> str:
        return self.config["family"]


def resolve_cell(bench: dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """Find every file a cell names.  Raises KeyError for an unknown cell
    and FileNotFoundError for a missing file."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"unknown workload {name!r}; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    config = load_json(os.path.join(bench_dir, "configs",
                                    wl["config"] + ".json"))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     wl["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "cells", name + ".json"))
    cell = Cell(
        name=name, chips=wl["chips"], config=config, traffic=traffic,
        limits=limits["limits"],
        end_to_end=list(bench["end_to_end"]),
        per_layer=list(bench["per_layer"]))
    cell.reference = load_module("reference", cell.family)
    cell.flops = load_module("flops", cell.family)
    return cell


def metric_reader(name: str):
    return load_module("metrics", name)


# -- configuration ------------------------------------------------------------

def register_config(config: dict) -> str:
    """Register the configuration with the program's model registry, under
    a name of the benchmark's own, and return the ``perception://`` ref."""
    from repro.models import ModelConfig, register
    fields = dict(config["model"])
    fields["name"] = "bench." + config["name"]
    for k, v in list(fields.items()):
        if isinstance(v, list):
            fields[k] = tuple(v)
    register(ModelConfig(**fields))
    return "perception://" + fields["name"]


def derived_seed(seed: int, *path: int) -> int:
    """A 31-bit seed drawn from ``seed`` (any size) and a path of tags, so
    weights, payloads and the check's sample never share a stream."""
    ss = np.random.SeedSequence([seed % (1 << 64), *path])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


# -- traffic ------------------------------------------------------------------

@dataclass
class Clip:
    """One generated drive clip: its records, in bag order, and its bag."""
    path: str
    topics: list                 # topic of each record
    timestamps: np.ndarray       # int64 ns
    payloads: list               # uint8 arrays
    drive_s: float


def clip_schedule(traffic: dict) -> list[tuple[str, int, int]]:
    """(topic, timestamp ns, bytes) of one clip, time-ordered.  Every
    topic fires at its own rate in phase from the clip's start, so every
    clip of a mix, and every seed, has the same sizes and arrivals."""
    clip_ns = int(round(traffic["clip_s"] * 1e9))
    recs = []
    for order, t in enumerate(traffic["topics"]):
        period = 1e9 / t["hz"]
        k = 0
        while int(round(k * period)) < clip_ns:
            recs.append((int(round(k * period)), order, t["topic"],
                         int(t["bytes"])))
            k += 1
    recs.sort()
    return [(topic, ts, nbytes) for ts, _, topic, nbytes in recs]


def make_clips(traffic: dict, seed: int, out_dir: str) -> list[Clip]:
    """Write the mix's pool of clips as bags under ``out_dir``; payload
    bytes are uniform random from ``seed``."""
    from repro.core import Bag
    sched = clip_schedule(traffic)
    rng = np.random.default_rng([seed % (1 << 64), 0xC11B])
    clips = []
    for c in range(traffic["clips"]):
        path = os.path.join(out_dir, f"clip{c:03d}.bag")
        payloads = [rng.integers(0, 256, n, dtype=np.uint8)
                    for _, _, n in sched]
        bag = Bag.open_write(path)
        for (topic, ts, _), data in zip(sched, payloads):
            bag.write(topic, ts, data.tobytes())
        bag.close()
        clips.append(Clip(path=path, topics=[s[0] for s in sched],
                          timestamps=np.array([s[1] for s in sched],
                                              np.int64),
                          payloads=payloads,
                          drive_s=float(traffic["clip_s"])))
    return clips


# -- the closed loop ----------------------------------------------------------

@dataclass
class SuiteRun:
    t_submit: float
    t_done: float
    verdicts: Optional[dict]
    error: Optional[str] = None


@dataclass
class Loop:
    """The suite the closed loop submits, again and again."""
    ref: str
    clips: list
    traffic: dict
    goldens: dict = field(default_factory=dict)

    def scenarios(self):
        from repro.core import Scenario
        return [Scenario(name=f"clip{i:03d}", bag_path=c.path,
                         user_logic=self.ref,
                         batch_size=self.traffic["batch_size"],
                         num_partitions=1,
                         golden_bag_path=self.goldens.get(f"clip{i:03d}"))
                for i, c in enumerate(self.clips)]

    def run_suite(self, timeout: float = 300.0) -> SuiteRun:
        from repro.core import ScenarioSuite
        suite = ScenarioSuite(self.scenarios(),
                              num_workers=self.traffic["num_workers"],
                              backend="thread",
                              scheduler_kwargs={
                                  "heartbeat_timeout": HEARTBEAT_S})
        t0 = time.perf_counter()
        try:
            verdicts = suite.run(timeout=timeout)
            err = None
        except Exception as e:      # noqa: BLE001 - a raised suite is a
            verdicts, err = None, repr(e)   # failed one, and is counted
        return SuiteRun(t0, time.perf_counter(), verdicts, err)

    def write_goldens(self, run: SuiteRun, out_dir: str) -> None:
        if run.verdicts is None:
            raise RuntimeError(f"warm-up suite failed: {run.error}")
        for name, v in run.verdicts.items():
            if v.status != "PASS":
                raise RuntimeError(f"warm-up suite: {v.summary()}")
            path = os.path.join(out_dir, f"golden-{name}.bag")
            with open(path, "wb") as f:
                f.write(v.report.output_image)
            self.goldens[name] = path

    def window(self, seconds: float, first=None) -> tuple[float, list]:
        """Run suites until one finishes at or after ``seconds``; returns
        the window's length and every suite run.  ``first`` wraps the
        window's first suite (the traced run profiles it)."""
        runs = []
        t0 = time.perf_counter()
        while True:
            if first is not None and not runs:
                runs.append(first(self.run_suite))
            else:
                runs.append(self.run_suite())
            if runs[-1].t_done - t0 >= seconds:
                return runs[-1].t_done - t0, runs


def count_outcomes(runs: list, n_scenarios: int) -> tuple[int, int]:
    """(attempted, failed): scenarios submitted, and those not PASS."""
    attempted = failed = 0
    for r in runs:
        attempted += n_scenarios
        if r.verdicts is None:
            failed += n_scenarios
        else:
            failed += sum(v.status != "PASS" for v in r.verdicts.values())
    return attempted, failed

