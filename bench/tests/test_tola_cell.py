"""The ``exp2-r600.tola`` cell as ``BENCHMARK.json`` lists it: what it
reports, what one of its units counts, and whole runs at a size a CPU holds
with the look for a chip skipped: sound, ``correct`` comes out true under
the configuration's own limits; with the timed path broken underneath, or
the bfloat16 reference in the program's place, it comes out false."""

import types

import numpy as np
import pytest

import repro.engine
import run
import traffic

CELL = "exp2-r600.tola"
READERS = {"engine_ms.tola", "plan_ms.tola", "replay_ms.tola",
           "pool_ms.tola", "realize_ms.tola", "device_idle_pct.tola",
           "rescore_ms.tola", "eval_roofline_pct.tola",
           # the sweep cell's readers, per TOLA run here
           "eval_device_ms.sweep", "plan_lookup_ms.sweep",
           "eval_host_ms.sweep"}


def _cell(n_jobs=40):
    cell = run.load_cell(CELL)
    cell["cfg"]["n_jobs"] = n_jobs
    return cell


def test_listed_cell_reports_cells_per_s_and_setup_s():
    cell = run.load_cell(CELL)
    assert cell["mix"]["unit"] == "tola_cells"
    assert [m["name"] for m in cell["end_to_end"]] == ["cells_per_s",
                                                      "setup_s"]
    assert {m["name"] for m in cell["per_layer"]} == READERS
    assert all(m["moves"] == "cells_per_s" for m in cell["per_layer"])
    # Every limit the reference check compares is set.
    assert set(cell["cfg"]["limits"]) == {
        "c0_p99", "c0_max", "c1_p99", "c1_max", "alpha_max",
        "chosen_mismatch", "weights_tv", "realized_gap", "selfowned_gap"}


@pytest.mark.parametrize("r_total, pool_iters, rounds", [
    (600, 1, 2), (600, 2, 3), (0, 1, 1)])
def test_a_run_counts_the_cells_of_its_engine_rounds(r_total, pool_iters,
                                                     rounds):
    cell = _cell(n_jobs=12)
    cell["cfg"]["r_total"] = r_total
    mix = dict(cell["mix"], backend="numpy", pool_iters=pool_iters,
               premade_units=2)
    unit = traffic.make(cell["cfg"], mix, seed=2 ** 32 + 3)
    assert unit.unit(0) == 12 * 175 * 2 * rounds
    assert len(unit.results[0][1]["C"]) == rounds
    assert unit.shapes["rounds"] == rounds
    assert unit.rates(3 * unit.cells, 3, 2.0) == {
        "cells_per_s": 3 * 12 * 175 * 2 * rounds / 2.0}


def _run(cell, seed=2 ** 33 + 101):
    result, _ = run.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                             require_chip=False, log=lambda *a: None)
    return result


def test_sound_run_is_correct(monkeypatch):
    # Off the chip ``auto`` resolves to the float64 oracle; the float32
    # device path is what the chip runs.
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "jax")
    result = _run(_cell())
    assert result["correct"], result
    assert result["backend"] == "jax"
    assert set(result["metrics"]) == {"cells_per_s", "setup_s"}
    assert result["metrics"]["cells_per_s"]["value"] > 0.0


def _altered_answer(monkeypatch):
    # In every market, one job under one policy paid on demand for all of
    # its work once more.
    from repro.engine import api

    orig = api._dispatch

    def dispatch(backend, gplan, batch, early_start, out, *a, **kw):
        orig(backend, gplan, batch, early_start, out, *a, **kw)
        out["ondemand_cost"][:, 0, 0] += gplan.workload[0]

    monkeypatch.setattr(api, "_dispatch", dispatch)


def _stale_refinement(monkeypatch):
    # The refinement round scores the dedicated pool again, as if no
    # availability query had been made.
    from repro.engine import api

    orig = api.evaluate_grid

    def evaluate_grid(*a, **kw):
        kw["availability"] = None
        return orig(*a, **kw)

    monkeypatch.setattr(repro.engine, "evaluate_grid", evaluate_grid)


def _unchanged_learner(monkeypatch):
    # The learner's update returns its state unchanged: the weights never
    # leave the uniform start.
    import importlib

    replay = importlib.import_module("repro.learn.replay")
    monkeypatch.setattr(replay, "update_state",
                        lambda kind, state, *a, **kw: state)


@pytest.mark.parametrize("fault", [_altered_answer, _stale_refinement,
                                   _unchanged_learner],
                         ids=["altered_answer", "stale_refinement",
                              "unchanged_learner"])
def test_broken_path_is_refused(monkeypatch, fault):
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "jax")
    fault(monkeypatch)
    assert not _run(_cell())["correct"]


def test_bfloat16_reference_in_the_programs_place_is_refused(monkeypatch):
    import ml_dtypes

    cell = _cell()
    made = []

    def control(jobs, policies, markets, r_total, seed, **kw):
        u = made[-1]
        ref = u.reference(seed, ml_dtypes.bfloat16)
        if u._rounds is not None:
            u._rounds.extend(ref["C"])
        return [types.SimpleNamespace(
            chosen=ref["chosen"][s], weights=ref["weights"][s],
            realized=types.SimpleNamespace(total_cost=ref["cost"][s],
                                           selfowned_work=ref["selfowned"][s]))
            for s in range(len(markets))]

    monkeypatch.setattr(traffic.load_kind("tola"), "run_tola_scenarios",
                        control)
    orig_make = traffic.make

    def make(cfg, mix, seed):
        made.append(orig_make(cfg, mix, seed))
        return made[-1]

    monkeypatch.setattr(traffic, "make", make)
    result = _run(cell)
    assert not result["correct"]
    over = [k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]]
    assert over, result["checks"]


def test_control_readings_fail_a_limit():
    import ml_dtypes

    cell = _cell()
    mix = dict(cell["mix"], backend="numpy", premade_units=2)
    unit = traffic.make(cell["cfg"], mix, seed=2 ** 32 + 7)
    unit.unit(0)
    got = unit.control(seed=5, dtype=ml_dtypes.bfloat16)
    lim = cell["cfg"]["limits"]
    assert any(not np.isfinite(got[k]) or got[k] > lim[k] for k in lim), (
        got, lim)
