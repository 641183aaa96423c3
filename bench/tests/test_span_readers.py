"""The per-layer readers of the program's own spans, on a synthetic ``Run``
with hand-made span totals: the value per unit, nothing with no units, and
nothing when the program opened none of the spans the reader reads (a
program that predates them)."""

import importlib.util
import os

import pytest

import run

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")


def _read(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(units, totals):
    return run.Run(None, [], units, {}, {}, None, totals)


# A sweep of 4 calls: spans the readers must skip sit beside the ones they
# read, and a parent span covers its children.
SWEEP = {"evaluate_grid": 7.2, "prepare_stream": 0.8, "plan.arrays": 0.1,
         "plan.fingerprint": 0.6, "plan.lookup": 0.02, "plan": 0.001,
         "eval": 6.4, "eval.stack": 0.03, "eval.wait": 6.2,
         "eval.fetch": 0.05, "eval.scatter": 0.08, "views": 0.01}
TOLA = {"tola": 9.0, "tola.round": 3.0, "tola.plans": 0.4, "tola.pool": 0.6,
        "tola.realize": 0.9, "tola.availability": 0.05, "replay": 0.9}

CASES = [
    ("plan_lookup_ms.sweep", SWEEP, 4, 1e3 * (0.1 + 0.6 + 0.02) / 4),
    ("eval_host_ms.sweep", SWEEP, 4, 1e3 * (6.4 - 6.2) / 4),
    ("realize_ms.tola", TOLA, 2, 1e3 * 0.9 / 2),
    ("pool_ms.tola", TOLA, 2, 1e3 * (0.4 + 0.6) / 2),
]


@pytest.mark.parametrize("name,totals,units,want", CASES,
                         ids=[c[0] for c in CASES])
def test_value_per_unit(name, totals, units, want):
    assert _read(name)(_run(units, totals)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name,totals", [(c[0], c[1]) for c in CASES],
                         ids=[c[0] for c in CASES])
def test_none_without_units(name, totals):
    assert _read(name)(_run(0, totals)) is None


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_none_when_the_spans_are_missing(name):
    # What a program without the new spans leaves: the older spans only.
    older = {"evaluate_grid": 7.2, "prepare_stream": 0.8, "plan": 0.001,
             "pool": 0.002, "eval": 6.4, "views": 0.01, "replay": 0.9}
    assert _read(name)(_run(4, older)) is None
    assert _read(name)(_run(4, {})) is None
