"""Critical-path breakdown of a suite ``trace.json`` flight recording.

``ScenarioSuite.run(trace=path)`` writes a Chrome/Perfetto trace of the
whole run — driver and worker spans stitched into one timeline.
Perfetto answers "what happened at t=1.38s"; this tool answers the
coarser engineering question: **where does each scenario's time go**,
stage by stage (read vs logic vs record vs transport vs cache
vs aggregate), and which stage dominates:

    PYTHONPATH=src python -m repro.tools.trace_report trace.json
    PYTHONPATH=src python -m repro.tools.trace_report trace.json --strict

Per scenario it prints each stage's busy time (double-count-free — see
:func:`repro.obs.export.stage_breakdown`), its share of the scenario's
staged total, and flags the dominant stage with ``<-- bottleneck`` when
it holds more than ``--dominant`` (default 0.5) of that total.  Spans
attributable to no scenario (suite-level cache probes, endpoint setup)
report under ``_suite``.

Integrity checks (what ``--strict`` gates on, the CI smoke shape):

* the trace contains at least one span event,
* no orphan parents — every span's parent id is either 0 (a root) or
  itself present in the trace.  A cross-process stitch that lost worker
  buffers, or a context annotation that failed to propagate, shows up
  here as orphans,
* ``incomplete`` spans (open at drain — normal for a crash recording)
  are reported, and tolerated, in both modes.

``--json out.json`` additionally writes the machine-readable analysis.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence

from repro.obs.export import events_to_records, stage_breakdown

__all__ = ["analyze", "load_events", "main", "render"]


def load_events(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    events = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a Chrome trace "
                         "(no traceEvents array)")
    return events


def analyze(events: Sequence[dict], dominant: float = 0.5) -> dict:
    """Stage breakdown + integrity summary of one exported trace."""
    records = events_to_records(events)
    ids = {r[0] for r in records}
    orphans = [r for r in records if r[1] and r[1] not in ids]
    incomplete = sum(1 for r in records if not r[5])
    pids = sorted({r[6] for r in records})
    by_scenario = stage_breakdown(records)

    scenarios: dict = {}
    for name, stages in sorted(by_scenario.items()):
        total = sum(stages.values())
        ranked = sorted(stages.items(), key=lambda kv: -kv[1])
        top, top_ns = ranked[0] if ranked else (None, 0)
        scenarios[name] = {
            "total_ns": total,
            "stages": dict(ranked),
            "bottleneck": (top if total and top_ns / total >= dominant
                           else None),
        }
    return {
        "spans": len(records),
        "processes": len(pids),
        "incomplete": incomplete,
        "orphans": [{"id": r[0], "parent": r[1], "name": r[2]}
                    for r in orphans],
        "scenarios": scenarios,
    }


def _fmt_ns(ns: int) -> str:
    if ns >= 1_000_000_000:
        return f"{ns / 1e9:.3f}s"
    if ns >= 1_000_000:
        return f"{ns / 1e6:.1f}ms"
    return f"{ns / 1e3:.0f}us"


def render(report: dict) -> str:
    lines = [f"trace: {report['spans']} spans across "
             f"{report['processes']} process(es)"
             + (f", {report['incomplete']} incomplete"
                if report["incomplete"] else "")]
    for name, entry in report["scenarios"].items():
        total = entry["total_ns"]
        lines.append(f"  {name}: staged total {_fmt_ns(total)}")
        for stage, ns in entry["stages"].items():
            share = (ns / total) if total else 0.0
            mark = ("  <-- bottleneck"
                    if stage == entry["bottleneck"] else "")
            lines.append(f"    {stage:<10} {_fmt_ns(ns):>10}  "
                         f"{share:6.1%}{mark}")
    if report["orphans"]:
        lines.append(f"{len(report['orphans'])} orphan span(s) — "
                     "broken stitch:")
        for o in report["orphans"][:10]:
            lines.append(f"  {o['name']} (id {o['id']}, "
                         f"missing parent {o['parent']})")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.tools.trace_report",
        description="Per-scenario per-stage breakdown of a "
                    "ScenarioSuite trace.json; flags the dominant "
                    "bottleneck stage.")
    parser.add_argument("trace", help="trace.json written by "
                                      "ScenarioSuite.run(trace=...)")
    parser.add_argument("--dominant", type=float, default=0.5,
                        help="flag a stage as the bottleneck when it "
                             "holds at least this share of its "
                             "scenario's staged time")
    parser.add_argument("--json", dest="json_out", default=None,
                        help="also write the analysis as JSON")
    parser.add_argument("--strict", action="store_true",
                        help="exit 1 on an empty trace or any orphan "
                             "span (CI smoke gate)")
    args = parser.parse_args(argv)
    report = analyze(load_events(args.trace), dominant=args.dominant)
    print(render(report))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.strict and (not report["spans"] or report["orphans"]):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
