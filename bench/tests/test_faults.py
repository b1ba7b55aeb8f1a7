"""A whole run, chip look aside, at test widths: sound, it is correct; with
the timed path broken underneath, or with the control in the program's
place, ``correct`` comes out false."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DATA

import run
from harness import load_json, resolve_cell
from reference.common import decode_features

CELLS = ["qwen3-small.tiny", "mamba-small.tiny"]
SEED = 2**33 + 5


def cell_of(name):
    return resolve_cell(load_json(f"{DATA}/BENCHMARK.json"), name, DATA)


def run_once(cell):
    return run.run_cell(cell, SEED, 0.0, False, {}, time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run_once(cell_of(name))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 2
    assert set(res["metrics"]) == {"replay_rate", "setup_s"}
    assert list(res)[-2:] == ["checks", "values"]


def patch_step(monkeypatch, fn):
    from repro.perception import PerceptionStep
    orig = PerceptionStep.step_arrays
    monkeypatch.setattr(PerceptionStep, "step_arrays",
                        lambda self, batch: fn(self, batch, orig))


@pytest.mark.parametrize("name", CELLS)
def test_altered_answers_are_caught(monkeypatch, name):
    def altered(self, batch, orig):
        logits, digests = orig(self, batch)
        return logits + 0.1 * jnp.abs(logits).max(), digests
    patch_step(monkeypatch, altered)
    res = run_once(cell_of(name))
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_half_the_batch_left_out_is_caught(monkeypatch, name):
    def half(self, batch, orig):
        rows = len(batch["lengths"])
        if rows < 2:
            return orig(self, batch)
        keep = {k: (v[:rows // 2] if isinstance(v, np.ndarray) else v)
                for k, v in batch.items()}
        return orig(self, keep)
    patch_step(monkeypatch, half)
    with pytest.raises(RuntimeError, match="warm-up suite"):
        run_once(cell_of(name))


def test_a_stall_past_the_default_heartbeat_loses_no_worker(monkeypatch):
    """The process stands still for 3 s in the warm-up suite: no heartbeat
    gets through and every step waits.  At the scheduler's 2 s default every
    worker would read as lost, the suite would raise and the run would end
    with no result; at the harness's window the run is sound."""
    from repro.core.scheduler import Scheduler
    lock = threading.Lock()
    stalled = threading.Event()
    ended = threading.Event()
    beat = Scheduler._on_beat
    monkeypatch.setattr(Scheduler, "_on_beat", lambda self, wid: (
        None if stalled.is_set() else beat(self, wid)))

    def stall(self, batch, orig):
        with lock:
            first = not stalled.is_set() and not ended.is_set()
            if first:
                stalled.set()
        if first:
            time.sleep(3.0)
            stalled.clear()
            ended.set()
        ended.wait()
        return orig(self, batch)
    patch_step(monkeypatch, stall)
    res = run_once(cell_of("qwen3-small.tiny"))
    assert ended.is_set()
    assert res["correct"] and res["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_caught(monkeypatch, name):
    """The reference at fp8, put where the program computes the logits."""
    cell = cell_of(name)
    d_model = cell.config["model"]["d_model"]

    def control(self, batch, orig):
        feats = np.stack([decode_features(
            p[:n], d_model) for p, n in zip(batch["payload"],
                                            batch["lengths"])])
        return jnp.asarray(cell.reference.forward(
            cell.config, self.seed, feats, "fp8")), None
    patch_step(monkeypatch, control)
    res = run_once(cell)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
