"""Per-layer metric readers, one file per metric of ``BENCHMARK.json``.

Each file defines ``read(r) -> float | None`` over a
:class:`devtrace.Readings`; ``None`` means the run held nothing to read, and
the harness then leaves the metric out of the result line.
"""
