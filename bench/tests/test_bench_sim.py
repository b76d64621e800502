"""The simulator cells' driver at sizes a CPU holds: a sound run is correct,
the timed path broken underneath is not, and neither is the control (the
reference with bfloat16 cycle counters, a precision below the float32 the
configuration states)."""
import dataclasses
import pathlib
import time

import jax
import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = {"traffic": {"accesses": 2000, "intervals": 3}}
CELLS = ("sim-gups-rainbow", "sim-gups-flat-static")


def run(cell, traced=False, seed=2**31 + 5):
    jax.clear_caches()  # a patched program must be traced again
    over = TINY
    if traced:  # tiny units: slice the trace from the window's start
        over = {"traffic": {**TINY["traffic"], "trace_slice_at_s": 0.0, "trace_slice_s": 0.3}}
    return harness.run_cell(ROOT, cell, seed, 0.5 if traced else 0.1, traced,
                            t_start=time.perf_counter(), require_chip=False, overrides=over,
                            log=lambda *a, **k: None)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = run(cell)
    assert line["correct"], line["checks"]
    assert line["checks"]["max_rel_gap"]["value"] == 0.0  # the reference follows the same order
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"sim_accesses_per_s", "setup_s"}


def test_traced_run_reads_per_layer_metrics():
    line = run("sim-gups-rainbow", traced=True)
    assert line["correct"] and line["device"]["busy_s"] > 0 and "breakdown" in line
    assert set(line["metrics"]) == {"sim.device_idle_share"}


def _state_unchanged(monkeypatch):
    from repro.engine import simloop

    monkeypatch.setattr(simloop, "engine_step",
                        lambda spec, state, chunk: (state, simloop._zero_stats()))


def _answer_altered(monkeypatch):
    from repro.sim import runner

    real = runner.finalize_metrics

    def altered(*a, **k):
        m = real(*a, **k)
        return dataclasses.replace(m, migrations=m.migrations + 1)

    monkeypatch.setattr(runner, "finalize_metrics", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    try:
        line = run(cell)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not line["correct"], line["checks"]
    assert line["failed"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    bench = harness.Bench(ROOT)
    c = bench.cell(cell)
    cfg = bench.config(c["config"])
    mix = {**bench.traffic(c["traffic"]), **TINY["traffic"]}
    lines = list(bench.driver("sim").readings(cfg, mix, [3, 2**31 + 9, 12], jax.devices(),
                                              bench.reference(cfg["reference"]), program=False))
    assert all(ln["control"]["max_rel_gap"] > mix["limits"]["max_rel_gap"] for ln in lines)
