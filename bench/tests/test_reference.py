"""The plain reference and the frozen stream against the program's own
float64 oracle, at a size a CPU holds: they must agree to rounding, or the
comparison that decides ``correct`` would judge the program by a wrong
yardstick."""

import json
import os

import numpy as np
import pytest

import reference
import stream

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _cfg(name="paper61-exp1-type4"):
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        return json.load(fh)


def test_frozen_stream_matches_program_generator():
    from repro.core.workload import generate_dag_jobs

    cfg = _cfg()
    ours = stream.generate(cfg, 40, 0)
    theirs = generate_dag_jobs(40, cfg["job_type"], 0)
    for a, b in zip(ours, theirs):
        assert a.arrival == b.arrival and a.deadline == b.deadline
        assert np.array_equal(a.z, [t.z for t in b.tasks])
        assert a.preds == tuple(tuple(int(p) for p in ps) for ps in b.preds)


@pytest.mark.parametrize("r_total, beta0", [
    (0, None),
    (600, [2 / 12, 4 / 14, 6 / 16, 8 / 18, 1 / 2, 0.6, 0.7]),
])
def test_reference_matches_program_float64_oracle(r_total, beta0):
    import traffic

    cfg = _cfg()
    cfg.update(n_jobs=30, r_total=r_total, x0=1.5 if r_total else 3.0)
    cfg["policy_grid"] = dict(cfg["policy_grid"], beta0=beta0)
    mix = {"unit": "evaluate_grid", "scenarios": 2, "backend": "numpy",
           "warmup_units": 0, "checked_markets": 4}
    sweep = traffic.make(cfg, mix, seed=2 ** 33 + 5)
    for k in range(2):
        sweep.unit(k)
    got = sweep.check(seed=1)
    assert got["cell_max"] < 1e-12, got


def test_tola_reference_matches_program_float64_oracle():
    import traffic

    cfg = _cfg("paper61-exp2-type1-r600")
    cfg["n_jobs"] = 60
    mix = {"unit": "tola", "scenarios": 2, "backend": "numpy",
           "learner": "hedge", "pool_iters": 1, "warmup_units": 0,
           "premade_units": 2, "checked_units": 2}
    tola = traffic.make(cfg, mix, seed=2 ** 33 + 5)
    for k in range(2):
        tola.unit(k)
    got = tola.check(seed=1)
    assert got["chosen_mismatch"] == 0.0, got
    assert max(got.values()) < 1e-12, got


def test_bfloat16_control_is_refused():
    import ml_dtypes

    cfg = _cfg()
    chains = [reference.chain(d.arrival, d.deadline, d.z, d.delta, d.preds)
              for d in stream.generate(cfg, 30, 0)]
    n = int(np.ceil((max(c[1] for c in chains) + 1.0) * 12)) + 1
    idx = np.arange(5, 7)
    ref = reference.unit_costs(cfg, chains, idx, n)
    low = reference.unit_costs(cfg, chains, idx, n, ml_dtypes.bfloat16)
    w = np.array([c[2].sum() for c in chains])
    got = reference.compare(low, ref, w)
    lim = cfg["limits"]
    assert any(got[k] > lim[k] for k in lim), (got, lim)
