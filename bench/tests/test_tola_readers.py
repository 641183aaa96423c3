"""The TOLA cell's two new per-layer readers, and the sweep's eval-device
reader that it shares, on synthetic spans and a synthetic device trace:
the value per run, nothing with no runs, and nothing where the program
left nothing to read (a program that predates the ``tola.rescore`` span,
or a window with no cost program on the device)."""

import importlib.util
import math
import os

import pytest

import devtrace
import run

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# The cell's shapes at 2,500 jobs: 175 policies in 65 eval groups over 5
# bids, L = 49 after the chain transform, 2 markets, two engine rounds.
SHAPES = {"J": 2500, "P": 175, "S": 2, "L": 49, "n_slots": 12_000,
          "bids": 5, "groups": 65, "rounds": 2}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace(modules):
    host = [(devtrace.WINDOW, 0.0, 10.0), (devtrace.UNIT, 0.0, 5.0),
            (devtrace.UNIT, 5.0, 10.0)]
    ops = [(f"%{n}.1 = f32[..] custom-call(..)", s, e) for n, s, e in modules]
    return devtrace.Trace({"host": host, "devices": [
        {"modules": modules, "ops": ops}]})


# Two runs: each a round-0 and a refinement launch of the chain kernel,
# beside plan and view programs the eval readers must skip.
MODULES = [("jit_policy_cost_chain(11)", 0.5, 0.6),
           ("jit_policy_cost_chain(12)", 2.0, 2.25),
           ("jit_policy_cost_chain(11)", 5.5, 5.6),
           ("jit_policy_cost_chain(12)", 7.0, 7.25),
           ("jit_views(3)", 0.2, 0.3), ("jit_groups(4)", 1.0, 1.5),
           ("jit_full(5)", 5.0, 5.4)]
TOLA = {"tola": 9.0, "tola.score": 1.5, "tola.rescore": 2.4,
        "evaluate_grid": 3.9, "tola.round": 4.0, "replay": 0.8}


def _run(units, totals=None, modules=MODULES, peaks=PEAKS):
    return run.Run(_trace(modules), [], units, SHAPES, {}, peaks,
                   totals or {})


def test_rescore_ms_per_run():
    read = _reader("rescore_ms.tola").read
    assert read(_run(2, TOLA)) == pytest.approx(1e3 * 2.4 / 2, rel=1e-12)
    assert read(_run(0, TOLA)) is None
    older = {k: v for k, v in TOLA.items() if k != "tola.rescore"}
    assert read(_run(2, older)) is None


def test_eval_device_ms_counts_both_rounds_only():
    read = _reader("eval_device_ms.sweep").read
    assert read(_run(2)) == pytest.approx(1e3 * (0.1 + 0.25), rel=1e-9)
    assert read(_run(0)) is None
    assert read(_run(2, modules=MODULES[4:])) is None


def test_refinement_round_reads_per_scenario_plans():
    m = _reader("eval_roofline_pct.tola")
    J, G, L, S, B, n = 2500, 65, 49, 2, 5, 12_000
    rows = G * J
    common = B * S * (n + 1) * 8 + S * rows * 16 + rows * 4
    shared = m.eval_bytes(J, G, L, S, B, n, per_scenario=False)
    refined = m.eval_bytes(J, G, L, S, B, n, per_scenario=True)
    assert shared == rows * L * 13 + common
    assert refined == rows * L * 4 + S * rows * L * 9 + common
    assert m.eval_ops(J, G, L, S, n) == S * rows * L * (2 * 14 + 40)
    # Each round is bound by its bytes; the run's least time is the sum.
    assert m.least_seconds(SHAPES, PEAKS) == pytest.approx(
        (shared + refined) / PEAKS["hbm_bytes_per_s"], rel=1e-12)


def test_eval_roofline_pct_per_run():
    m = _reader("eval_roofline_pct.tola")
    want = 100.0 * m.least_seconds(SHAPES, PEAKS) / (0.1 + 0.25)
    assert m.read(_run(2)) == pytest.approx(want, rel=1e-9)
    assert 0.0 < m.read(_run(2)) < 100.0
    assert m.read(_run(0)) is None
    assert m.read(_run(2, peaks=None)) is None
    assert m.read(_run(2, modules=MODULES[4:])) is None


def test_one_round_without_a_pool():
    m = _reader("eval_roofline_pct.tola")
    one = dict(SHAPES, rounds=1)
    b = m.eval_bytes(2500, 65, 49, 2, 5, 12_000, per_scenario=False)
    assert math.isclose(m.least_seconds(one, PEAKS),
                        b / PEAKS["hbm_bytes_per_s"])
