"""TOLA (Alg. 4) on a self-owned pool, the path of the benchmark's
``exp2-r600.tola`` cell, at a size a CPU holds.

* ``run_tola_scenarios`` on every engine backend against the benchmark's
  plain float64 reference (``bench/reference.py::tola``) and the device
  backends against the numpy oracle, on seeded markets of the cell's own
  configuration cut to 40 jobs;
* the spans around each round's engine call: ``tola.score`` once,
  ``tola.rescore`` once per pool refinement;
* the gauge ``tola.selfowned_share{round}`` and the counter
  ``engine.plan.availability_windows`` against values worked out from the
  realized run and from the plan's shapes.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import repro.engine
from repro import obs
from repro.core import Policy, run_tola_scenarios
from repro.core.transform import transform
from repro.core.types import DAGJob, Task
from repro.engine import ScenarioSpec, evaluate_grid
from repro.obs import METRICS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")
CONFIG = os.path.join(BENCH, "configs", "paper61-exp2-type1-r600-tola.json")
J, S, FIRST = 40, 2, 2 ** 30 + 12_345


def _bench_module(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # its dataclasses look themselves up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cell():
    """The cell's stream, grid and markets, built for the program and for
    the reference each in its own way."""
    reference, stream = _bench_module("reference"), _bench_module("stream")
    with open(CONFIG) as fh:
        cfg = dict(json.load(fh), n_jobs=J)
    dags = stream.generate(cfg, J, cfg["stream_seed"])
    jobs = [transform(DAGJob(
        arrival=d.arrival, deadline=d.deadline,
        tasks=tuple(Task(z=float(z), delta=float(dl))
                    for z, dl in zip(d.z, d.delta)),
        preds=d.preds)) for d in dags]
    chains = [reference.chain(d.arrival, d.deadline, d.z, d.delta, d.preds)
              for d in dags]
    m = cfg["market"]
    spec = ScenarioSpec(
        "fresh", max(j.deadline for j in jobs) + 1.0, 2 ** 31 - 1,
        seed=m["seed"], slots_per_unit=m["slots_per_unit"],
        p_ondemand=m["p_ondemand"], price_mean=m["price_mean"],
        price_lo=m["price_lo"], price_hi=m["price_hi"])
    n_slots = int(np.ceil((max(c[1] for c in chains) + 1.0)
                          * m["slots_per_unit"])) + 1
    idx = FIRST + np.arange(S)
    return {
        "cfg": cfg, "jobs": jobs,
        "policies": [Policy(beta=b2, bid=b, beta0=b0)
                     for b2, b, b0 in reference.policy_grid(cfg)],
        "markets": spec.materialize(FIRST, FIRST + S),
        "reference": reference,
        "ref": reference.tola(cfg, chains, idx, idx, n_slots),
        "workload": np.array([c[2].sum() for c in chains]),
    }


def _run(cell, backend, monkeypatch):
    """One TOLA run as the benchmark's unit makes it, with each round's
    cost tensor kept as the engine returns it."""
    inner, rounds = repro.engine.evaluate_grid, []

    def keep(*a, **kw):
        r = inner(*a, **kw)
        rounds.append(np.asarray(r.unit_cost))
        return r

    with monkeypatch.context() as mp:
        mp.setattr(repro.engine, "evaluate_grid", keep)
        res = run_tola_scenarios(
            cell["jobs"], cell["policies"], cell["markets"],
            cell["cfg"]["r_total"], seed=FIRST, pool_iters=1,
            backend=backend, learner="hedge")
    return {"C": rounds,
            "chosen": np.stack([r.chosen for r in res]),
            "weights": np.stack([r.weights for r in res]),
            "cost": np.stack([r.realized.total_cost for r in res]),
            "selfowned": np.stack([r.realized.selfowned_work for r in res])}


@pytest.fixture(scope="module")
def oracle(cell):
    with pytest.MonkeyPatch.context() as mp:
        return _run(cell, "numpy", mp)


def test_numpy_oracle_matches_reference(cell, oracle):
    got = cell["reference"].compare_tola(oracle, cell["ref"],
                                         cell["workload"])
    assert got["chosen_mismatch"] == 0.0, got
    assert max(got.values()) < 1e-12, got


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_device_backends_match_reference_and_oracle(cell, oracle, backend,
                                                    monkeypatch):
    pytest.importorskip("jax")
    got = _run(cell, backend, monkeypatch)
    assert len(got["C"]) == 2
    # Two pins of the f32 device rounds: the float64 reference, within the
    # cell's own limits, and the program's float64 oracle, just as close.
    limits = cell["cfg"]["limits"]
    compare = cell["reference"].compare_tola
    for other in (cell["ref"], oracle):
        nums = compare(got, other, cell["workload"])
        assert all(nums[k] <= limits[k] for k in limits), (nums, limits)
    # Most cells round alike; the drawn policies are the oracle's.
    assert np.array_equal(got["chosen"], oracle["chosen"])


@pytest.mark.parametrize("pool_iters", [1, 2])
@pytest.mark.parametrize("entry", ["single_market", "all_markets"])
def test_each_engine_call_has_its_round_span(cell, entry, pool_iters):
    jobs, pols = cell["jobs"][:12], cell["policies"][::25]
    markets = cell["markets"][:1] if entry == "single_market" \
        else cell["markets"]
    with obs.tracing() as tr:
        run_tola_scenarios(jobs, pols, markets, 600, pool_iters=pool_iters,
                           backend="numpy")
    (root,) = tr.roots()
    (score,) = tr.named("tola.score")
    rescores = tr.named("tola.rescore")
    assert [r.attrs["round"] for r in rescores] == list(
        range(1, pool_iters + 1))
    assert {r.parent for r in [score] + rescores} == {root.id}
    assert [r.parent for r in tr.named("evaluate_grid")] == [
        r.id for r in [score] + rescores]


def test_no_rescore_without_a_pool(cell):
    with obs.tracing() as tr:
        run_tola_scenarios(cell["jobs"][:12], cell["policies"][::25],
                           cell["markets"], 0, pool_iters=1,
                           backend="numpy")
    assert len(tr.named("tola.score")) == 1
    assert tr.named("tola.rescore") == []


def _share(results):
    return np.mean([r.realized.selfowned_work.sum()
                     / r.realized.workload.sum() for r in results])


def test_selfowned_share_gauge_is_the_realized_share(cell):
    args = (cell["jobs"], cell["policies"], cell["markets"], 600)
    with METRICS.collecting(reset=True):
        res = run_tola_scenarios(*args, seed=FIRST, pool_iters=1,
                                 backend="numpy")
    gauge = METRICS.gauge("tola.selfowned_share")
    # Round 0 alone draws what the first round of a refined run draws.
    first = run_tola_scenarios(*args, seed=FIRST, pool_iters=0,
                               backend="numpy")
    assert gauge.value(round=0) == pytest.approx(_share(first), rel=1e-12)
    assert gauge.value(round=1) == pytest.approx(_share(res), rel=1e-12)
    assert 0.0 < gauge.value(round=1) < 1.0
    # Work done on self-owned instances, by the realized run's own pieces.
    own = np.mean([r.realized.selfowned_work.sum() for r in res])
    z = sum(j.total_work for j in cell["jobs"])
    assert gauge.value(round=1) * z == pytest.approx(own, rel=1e-9)


def test_single_market_selfowned_share(cell):
    with METRICS.collecting(reset=True):
        res = run_tola_scenarios(cell["jobs"], cell["policies"],
                                 cell["markets"][:1], 600, seed=3,
                                 pool_iters=1, backend="numpy")
    assert METRICS.gauge("tola.selfowned_share").value(round=1) \
        == pytest.approx(_share(res), rel=1e-12)


def _akeys(policies, r_total):
    """Distinct (Dealloc parameter, beta_0) pairs of a grid: one plan and
    one availability query per market each."""
    return len({(round(p.dealloc_param(r_total), 12), p.beta0)
                for p in policies})


@pytest.mark.parametrize("plan_backend,backend", [("host", "numpy"),
                                                  ("device", "jax")])
def test_availability_windows_counter(cell, plan_backend, backend):
    if backend == "jax":
        pytest.importorskip("jax")
    jobs, pols = cell["jobs"], cell["policies"]
    seen = []

    def query(starts, ends):
        seen.append(np.shape(starts))
        return np.full(np.shape(starts), 300.0)

    kw = dict(availability=[query] * S, backend=backend,
              plan_backend=plan_backend)
    METRICS.reset()
    evaluate_grid(jobs, pols, cell["markets"], 600, **kw)
    assert "engine.plan.availability_windows" not in METRICS.snapshot()
    seen.clear()
    with METRICS.collecting(reset=True):
        evaluate_grid(jobs, pols, cell["markets"], 600, **kw)
    L = max(j.l for j in jobs)
    want = _akeys(pols, 600) * S * len(jobs) * L
    assert sum(int(np.prod(s)) for s in seen) == want
    assert METRICS.counter("engine.plan.availability_windows").value() \
        == want


def test_availability_windows_of_a_tola_run(cell):
    with METRICS.collecting(reset=True):
        run_tola_scenarios(cell["jobs"], cell["policies"], cell["markets"],
                           600, pool_iters=2, backend="numpy")
    L = max(j.l for j in cell["jobs"])
    # Round 0 queries nothing; each refinement queries every (plan, beta_0)
    # pair's windows once per market.
    assert METRICS.counter("engine.plan.availability_windows").value() \
        == 2 * _akeys(cell["policies"], 600) * S * J * L
