"""`correct`: the program passes, its control and planted faults fail.

At a size a CPU test holds: 16^2 die cells with a 4-cell margin, a
sweep of 8 intervals on a 16^2 die grid, and 1024 AP words.  The same code decides `correct` on the chip at the
cells' own sizes.
"""
import dataclasses
import time

import jax
import numpy as np
import pytest

from bench import control, registry, run

SEED = 2 ** 31 + 9


def small(name: str) -> registry.Cell:
    cell = registry.Cell(name)
    if cell.traffic["kind"] == "sweep":
        cell.traffic.update(grid_n=16, n_intervals=8)
    else:
        cell.traffic.update(die_cells=16, margin_cells=4)
    cell.config["ap"]["n_words"] = 1024
    return cell


CELLS = ["paper-sweep-64-simd", "paper-steady-256", "paper-ap-2e20"]


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_its_control_fails(name):
    cell = small(name)
    (line,) = control.readings(cell, [SEED], 0.5, jax.devices())
    assert line["failed"] == 0 and line["program"]
    for key, value in line["program"].items():
        assert value <= cell.limits[key], (key, value)
    assert any(v > cell.limits[k] for k, v in line["control"].items()), line


def _alter_temperature(thermal):
    real = thermal.steady_state_stats

    def broken(power, grid, **kw):
        T, stats = real(power, grid, **kw)
        return T.at[0, 0, 0].add(1.0), stats
    return broken


def _temperature_unchanged(thermal):
    real = thermal.steady_state_stats

    def broken(power, grid, **kw):
        T, stats = real(power, grid, **kw)
        return T * 0 + thermal.AMBIENT_C, stats
    return broken


def _alter_word(engine):
    real = engine.APEngine.read

    def broken(self, field, signed=False):
        out = real(self, field, signed)
        out[0] ^= 1
        return out
    return broken


def _run_unchanged(engine):
    return lambda self, sched: None


def _alter_replay(feedback):
    real = feedback.replay_cases

    def broken(*a, **kw):
        reports = real(*a, **kw)
        label = sorted(reports)[0]
        rep = reports[label]
        peak = rep.peak_C.copy()
        peak[-1, 0] += 1.0
        reports[label] = dataclasses.replace(rep, peak_C=peak)
        return reports
    return broken


def _replay_unchanged(feedback):
    real = feedback.replay_cases

    def broken(*a, **kw):
        return {k: dataclasses.replace(r, peak_C=r.peak_C * 0 + 45.0,
                                       min_C=r.min_C * 0 + 45.0)
                for k, r in real(*a, **kw).items()}
    return broken


FAULTS = {
    "sweep answer altered": ("paper-sweep-64-simd", "repro.stack.feedback",
                             "replay_cases", _alter_replay),
    "sweep state unchanged": ("paper-sweep-64-simd", "repro.stack.feedback",
                              "replay_cases", _replay_unchanged),
    "steady answer altered": ("paper-steady-256", "repro.core.thermal",
                              "steady_state_stats", _alter_temperature),
    "steady state unchanged": ("paper-steady-256", "repro.core.thermal",
                               "steady_state_stats", _temperature_unchanged),
    "ap word altered": ("paper-ap-2e20", "repro.core.engine",
                        "APEngine.read", _alter_word),
    "ap pass schedule leaves the state unchanged": (
        "paper-ap-2e20", "repro.core.engine", "APEngine.run", _run_unchanged),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """Drives a whole run past the harness's look for a chip."""
    import importlib
    name, module, attr, make = FAULTS[fault]
    mod = importlib.import_module(module)
    owner, _, leaf = attr.rpartition(".")
    target = getattr(mod, owner) if owner else mod
    monkeypatch.setattr(target, leaf, make(mod))
    res = run.run_cell(small(name), SEED, 0.5, False, jax.devices(),
                       time.perf_counter())
    assert res["attempted"] > 0
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct_and_reports_its_metrics(name):
    cell = small(name)
    res = run.run_cell(cell, SEED, 0.5, False, jax.devices(),
                       time.perf_counter())
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_a_traced_run_is_capped_and_still_checked(monkeypatch):
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.3)
    res = run.run_cell(small("paper-steady-256"), SEED, 30.0, True,
                       jax.devices(), time.perf_counter())
    assert res["correct"] is True
    assert res["device"]["window_s"] < 5.0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "vcycles_per_solve.solve" in res["metrics"]
