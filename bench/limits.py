"""Read the numbers the correctness limits are set from, on the chip.

    python3 bench/limits.py --workload <name> --seeds 11,12,... \\
        --control-seeds 11,12,13 --out limits-<name>.json

For each seed, in one process: the cell's set-up with that seed, one suite
at the cell's own load, and the check of ``check.py``.  For the control
seeds the reference is also computed in fp8 in the program's place (the
control).  The lower reading is the largest ``logit_gap`` of the program;
the upper reading the smallest gap of the control.  Writes every seed's values
and both readings to ``--out``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    import run
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from harness import load_benchmark, resolve_cell
    cell = resolve_cell(load_benchmark(), args.workload)
    peaks = run.find_chip(cell.chips)
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        res = run.run_cell(cell, seed, 0.0, False, peaks, t0,
                           control=("fp8",) if seed in ctrl else ())
        row = {"seed": seed, "correct": res["correct"],
               "values": res["values"],
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        run.log(json.dumps(row))
    gaps = [r["values"]["logit_gap"] for r in rows]
    out = {"workload": cell.name, "rows": rows,
           "lower": max(gaps),
           "upper": min((r["values"]["control_gap.fp8"] for r in rows
                         if "control_gap.fp8" in r["values"]), default=None)}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("workload", "lower", "upper")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
