"""Whole runs of each cell at a size a CPU holds, with the look for a chip
skipped: sound, ``correct`` comes out true; with the timed path broken
underneath (or the bfloat16 reference in the program's place), it comes
out false."""

import json
import os
import types

import numpy as np
import pytest

import reference
import run
import traffic

SWEEP, TOLA = "exp1-type4.market-sweep", "exp2-r600.tola"


def _tola_cell():
    """The TOLA cell as its files define it: it is not yet among
    ``BENCHMARK.json``'s cells."""
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*path):
        with open(os.path.join(bench, *path)) as fh:
            return json.load(fh)

    return {"name": TOLA, "chips": 1,
            "cfg": load("configs", "paper61-exp2-type1-r600.json"),
            "mix": load("traffic", "tola.json"),
            "end_to_end": [{"name": "tola_s", "unit": "s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def _cell(workload):
    cell = _tola_cell() if workload == TOLA else run.load_cell(workload)
    cell["cfg"]["n_jobs"] = 40
    return cell


def _run(cell, seed=2 ** 32 + 17):
    result, checks = run.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                                  require_chip=False, log=lambda *a: None)
    assert list(result)[-1] == "checks"
    return result


@pytest.fixture
def f32_path(monkeypatch):
    # Off the chip ``auto`` resolves to the float64 oracle; the float32
    # device path is what the chip runs.
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "jax")


@pytest.mark.parametrize("workload", [SWEEP, TOLA])
def test_sound_run_is_correct(f32_path, workload):
    result = _run(_cell(workload))
    assert result["correct"], result
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["backend"] == "jax"


def _broken_dispatch(monkeypatch, fault):
    from repro.engine import api

    orig = api._dispatch

    def dispatch(backend, gplan, batch, early_start, out, *a, **kw):
        orig(backend, gplan, batch, early_start, out, *a, **kw)
        fault(out, gplan)

    monkeypatch.setattr(api, "_dispatch", dispatch)


def _altered_answer(out, gplan):
    # In every market, one job under one policy paid on demand for all of
    # its work once more.
    out["ondemand_cost"][:, 0, 0] += gplan.workload[0]


def _half_batch(out, gplan):
    # The second half of the jobs never scored.
    for v in out.values():
        v[:, v.shape[1] // 2:, :] = 0.0


@pytest.mark.parametrize("workload", [SWEEP, TOLA])
@pytest.mark.parametrize("fault", [_altered_answer, _half_batch],
                         ids=["altered_answer", "half_batch"])
def test_broken_eval_is_refused(f32_path, monkeypatch, fault, workload):
    _broken_dispatch(monkeypatch, fault)
    assert not _run(_cell(workload))["correct"]


def test_unchanged_state_is_refused(f32_path, monkeypatch):
    # Every call hands back the first call's tensor: stale markets.
    mod = traffic.load_kind("evaluate_grid")
    orig = mod.evaluate_grid
    first = []

    def stale(*a, **kw):
        r = orig(*a, **kw)
        first.append(r)
        return first[0]

    monkeypatch.setattr(mod, "evaluate_grid", stale)
    assert not _run(_cell(SWEEP))["correct"]


def test_unchanged_learner_state_is_refused(f32_path, monkeypatch):
    # The learner's update returns its state unchanged: the weights never
    # leave the uniform start.
    import importlib

    replay = importlib.import_module("repro.learn.replay")
    monkeypatch.setattr(replay, "update_state",
                        lambda kind, state, *a, **kw: state)
    assert not _run(_cell(TOLA))["correct"]


def _bf16_sweep(monkeypatch, cell):
    import ml_dtypes

    mod = traffic.load_kind("evaluate_grid")

    def control(jobs, policies, window, r_total, backend):
        st = control.unit.st
        idx = window.start + np.arange(window.n_scenarios)
        u = reference.unit_costs(cell["cfg"], st.chains, idx, st.ref_n_slots,
                                 ml_dtypes.bfloat16)
        return types.SimpleNamespace(unit_cost=u, backend="control")

    monkeypatch.setattr(mod, "evaluate_grid", control)
    return control


def _bf16_tola(monkeypatch, cell):
    import ml_dtypes

    mod = traffic.load_kind("tola")

    def control(jobs, policies, markets, r_total, seed, **kw):
        u = control.unit
        ref = u.reference(seed, ml_dtypes.bfloat16)
        if u._rounds is not None:
            u._rounds.extend(ref["C"])
        return [types.SimpleNamespace(
            chosen=ref["chosen"][s], weights=ref["weights"][s],
            realized=types.SimpleNamespace(total_cost=ref["cost"][s],
                                           selfowned_work=ref["selfowned"][s]))
            for s in range(len(markets))]

    monkeypatch.setattr(mod, "run_tola_scenarios", control)
    return control


@pytest.mark.parametrize("workload, put", [(SWEEP, _bf16_sweep),
                                           (TOLA, _bf16_tola)],
                         ids=[SWEEP, TOLA])
def test_bfloat16_reference_in_the_programs_place_is_refused(
        monkeypatch, workload, put):
    cell = _cell(workload)
    control = put(monkeypatch, cell)
    orig_make = traffic.make

    def make(cfg, mix, seed):
        control.unit = orig_make(cfg, mix, seed)
        return control.unit

    monkeypatch.setattr(traffic, "make", make)
    assert not _run(cell)["correct"]
