"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV rows:
    bag_cache_*        — paper Fig 6 (ROSBag memory cache vs disk) plus
                         the content-addressed result-cache suite race
                         (cold replay vs warm rehydration); writes
                         ``BENCH_bag_cache.json`` at the repo root
                         (warm must be >= 5x cold with bit-identical
                         verdicts — gated by ``--check`` in CI)
    scalability_*      — paper Fig 7 + §4.2 extrapolation
    scenario_matrix_*  — batched vs per-message replay × executor backend;
                         also writes machine-readable
                         ``BENCH_scenario_matrix.json`` at the repo root
                         (msgs/s per backend × batch size) so the perf
                         trajectory is tracked across PRs
    aggregation_*      — result-aggregation stages (k-way shard merge,
                         single-pass metrics/checksums, golden compare,
                         fused vs two-pass metrics race); writes
                         ``BENCH_aggregation.json`` at the repo root
    pipeline_*         — staged (queued-bus) vs synchronous replay with a
                         deliberately slow subscriber; writes
                         ``BENCH_pipeline.json`` (checksums + suite
                         verdicts asserted bit-identical across modes)
    transport_*        — bridged (loopback TCP LaneTransport -> RemoteBus)
                         vs in-process bus throughput with the stock sink
                         set; writes ``BENCH_transport.json`` (checksums
                         + export/import routing verdicts asserted
                         bit-identical across carriers)
    perception_*       — zero-copy device path: message-path vs
                         frame_to_batch vs fused decode→forward jit with
                         donated buffers; writes ``BENCH_perception.json``
                         (input checksums + suite verdicts asserted
                         bit-identical across all three consumers)
    shm_*              — same-host zero-copy data plane: recycled
                         segment-pool spill vs temp-file spill, shm ring
                         vs loopback-TCP framing; writes
                         ``BENCH_shm.json`` (``--check`` gates shm spill
                         >= 1.5x file and ring >= 1.3x loopback, with
                         verdicts bit-identical across carriers and
                         backends and zero leaked segments)
    binpipe_*          — paper Fig 4 (BinPipedRDD stage throughput)
    chaos_*            — clean suite vs the same suite under a seeded
                         fault plan (worker crash, lane stall, poison
                         user logic); writes ``BENCH_chaos.json``
                         (``--check`` gates that exactly the poisoned
                         scenarios + DAG downstream degrade to ERROR and
                         every survivor is bit-identical)
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    from benchmarks import (aggregation, bag_cache, binpipe, chaos,
                            perception, pipeline, scalability,
                            scenario_matrix, shm, transport)
    failures = 0
    for mod in (bag_cache, scalability, scenario_matrix, aggregation,
                pipeline, transport, shm, perception, binpipe, chaos):
        try:
            mod.main(csv=True)
        except Exception:  # noqa: BLE001
            failures += 1
            name = mod.__name__.split(".")[-1]
            print(f"{name}_FAILED,0.0,{traceback.format_exc(limit=1)!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
