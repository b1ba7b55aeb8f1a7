"""The layout of ``BENCHMARK.json``: every name resolves to its files, the
names and units keep to their characters, and every per-layer metric's
``moves`` is reported where the metric is."""

import json
import os
import re
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT

from harness import load_benchmark, metric_reader, resolve_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH_JSON = load_benchmark()
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]


def test_top_level_keys():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert BENCH_JSON["paths"] == ["bench"]
    assert BENCH_JSON["command"][1] == "bench/run.py"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = resolve_cell(BENCH_JSON, cell)
    assert c.reference.forward and c.flops.step_flops
    assert 0 < c.limits["logit_gap"] < 1
    for m in c.per_layer:
        assert callable(metric_reader(m["name"]).read)
    cfg = next(x for x in BENCH_JSON["configs"]
               if x["name"] == c.config["name"])
    assert os.path.exists(os.path.join(ROOT, cfg["file"]))


def test_names_and_units():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH_JSON[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in BENCH_JSON["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(names) == len(set(names))


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in BENCH_JSON["end_to_end"]}
    for cell in CELLS:
        c = resolve_cell(BENCH_JSON, cell)
        mine = {m["name"] for m in c.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2 and c.per_layer
        for m in c.per_layer:
            assert m["moves"] in e2e and m["moves"] in mine, (cell, m)


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        resolve_cell(BENCH_JSON, "no-such.cell")
    with pytest.raises(FileNotFoundError):
        metric_reader("no_such_metric")


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "cpu" in p.stderr and not p.stdout.strip()


def test_configs_name_their_files():
    for cfg in BENCH_JSON["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        assert body["name"] == cfg["name"]
        assert body["source"].startswith(cfg["source"])
