"""Unified observability layer: span tracing + a metrics registry.

The platform spans five subsystems (scheduler, queued bus, wire/shm
transports, result cache, chaos) whose health used to live in ad-hoc
counters scattered across classes.  This package gives them one home:

* :mod:`repro.obs.trace` — a low-overhead span tracer.  Per-thread
  lock-free ring buffers of ``(span_id, parent, name, cat, t0, t1,
  attrs)`` records; trace context crosses the process boundary inside
  task payloads and crosses the wire/shm frame grammar as a
  frame-header annotation; worker-side buffers ship back through the
  existing result path and stitch into one driver-side timeline.
  Disabled (the default) every instrumented seam is a single module
  attribute read + ``None`` check — the same zero-cost idiom as
  :func:`repro.chaos.active_plan`.

* :mod:`repro.obs.metrics` — counters/gauges/histograms behind
  per-component :class:`~repro.obs.metrics.Scope` objects registered
  with one process-wide registry, so a suite-level ``snapshot()`` can
  be persisted into the verdict manifest.

* :mod:`repro.obs.compiles` — the process's one JAX compile listener,
  behind the ``jit`` metrics scope and the ``jax.compile`` span.

* :mod:`repro.obs.export` — Chrome/Perfetto ``trace.json`` writer
  (load the file at https://ui.perfetto.dev) consumed by the
  ``repro.tools.trace_report`` critical-path CLI.

Entry points: ``ScenarioSuite.run(trace="trace.json")`` records a full
suite flight; :func:`repro.obs.trace.enable` / ``disable`` manage the
tracer directly for custom harnesses.
"""

from __future__ import annotations

from . import compiles, export, metrics, trace
from .metrics import Counter, Gauge, Histogram, Registry, Scope
from .trace import Tracer, disable, enable, enabled, get_tracer, span

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Scope", "Tracer",
    "compiles", "disable", "enable", "enabled", "export", "get_tracer",
    "metrics", "span", "trace",
]
