"""Backend-dispatching evaluation engine: numpy/jax/pallas parity on the
cost matrix, scenario-axis semantics, shared-pool sweep equivalence, and
the legacy-path routing (cost_matrix / evaluate_policy_fullpool)."""

import numpy as np
import pytest

from repro.core import (
    Policy,
    SpotMarket,
    generate_chain_jobs,
    run_jobs,
    selfowned_policies,
    spot_od_policies,
)
from repro.core.scheduler import evaluate_policy_fullpool
from repro.core.tola import run_tola_scenarios
from repro.engine import (
    available_backends,
    evaluate_grid,
    make_scenarios,
    replay_scenarios,
    resolve_backend,
)

TOL = 1e-5


def _setup(n=25, jt=1, seed=5, mseed=7):
    jobs = generate_chain_jobs(n, job_type=jt, seed=seed)
    market = SpotMarket(max(j.deadline for j in jobs) + 1, seed=mseed)
    return jobs, market


def _grid():
    return spot_od_policies()[:6] + selfowned_policies()[:6]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_backend_parity_randomized_streams(backend, seed):
    """numpy vs jax vs pallas(interpret) agree on the cost matrix to 1e-5."""
    jobs, m = _setup(seed=seed, mseed=seed + 10)
    ref = evaluate_grid(jobs, _grid(), m, r_total=60, backend="numpy")
    got = evaluate_grid(jobs, _grid(), m, r_total=60, backend=backend)
    np.testing.assert_allclose(got.matrix, ref.matrix, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_backend_parity_planned_starts(backend):
    """early_start=False (per-task windows) path, even windows + naive."""
    jobs, m = _setup(jt=2)
    kw = dict(r_total=40, windows="even", selfowned="naive",
              early_start=False, pool="shared")
    ref = evaluate_grid(jobs, _grid(), m, backend="numpy", **kw)
    got = evaluate_grid(jobs, _grid(), m, backend=backend, **kw)
    np.testing.assert_allclose(got.matrix, ref.matrix, atol=TOL, rtol=TOL)


def test_scenario_axis_reduces_to_single_market():
    """S=1 scenario list gives exactly the single-market result."""
    jobs, m = _setup()
    single = evaluate_grid(jobs, _grid(), m, r_total=30, backend="numpy")
    listed = evaluate_grid(jobs, _grid(), [m], r_total=30, backend="numpy")
    assert single.single_market and not listed.single_market
    np.testing.assert_array_equal(listed.unit_cost[0], single.matrix)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_scenario_batch_matches_per_scenario(backend):
    """Batching S markets in one pass == evaluating each market alone."""
    jobs, m = _setup()
    markets = make_scenarios(m.horizon, 3, seed=21, kind="regime")
    batched = evaluate_grid(jobs, _grid(), markets, r_total=30,
                            backend=backend)
    for s, ms in enumerate(markets):
        alone = evaluate_grid(jobs, _grid(), ms, r_total=30,
                              backend="numpy")
        np.testing.assert_allclose(batched.unit_cost[s], alone.matrix,
                                   atol=TOL, rtol=TOL)


def test_engine_matches_legacy_fullpool_loop():
    """The engine's dedicated-pool numpy path is bit-identical to the
    per-policy evaluate_policy_fullpool loop it replaced."""
    jobs, m = _setup(jt=3)
    pols = _grid()
    res = evaluate_grid(jobs, pols, m, r_total=50, backend="numpy")
    for p, pol in enumerate(pols):
        costs = evaluate_policy_fullpool(jobs, pol, m, r_total=50)
        np.testing.assert_array_equal(res.total_cost[0, :, p],
                                      costs.total_cost)
        np.testing.assert_array_equal(res.workload, costs.workload)


def test_shared_pool_matches_run_jobs():
    """pool="shared" replicates the realized run_jobs sweep semantics."""
    jobs, m = _setup(jt=2)
    pols = selfowned_policies()[::29]
    res = evaluate_grid(jobs, pols, m, r_total=60, pool="shared",
                        backend="numpy")
    for p, pol in enumerate(pols):
        costs = run_jobs(jobs, pol, m, r_total=60)
        np.testing.assert_array_equal(res.total_cost[0, :, p],
                                      costs.total_cost)
        np.testing.assert_array_equal(res.selfowned_work[:, p],
                                      costs.selfowned_work)


def test_cost_matrix_routes_through_engine():
    jobs, m = _setup()
    pols = _grid()
    C = run_tola_scenarios(jobs, pols, [m], r_total=30, pool_iters=0,
                           backend="numpy")[0].cost_matrix
    res = evaluate_grid(jobs, pols, m, r_total=30, backend="numpy")
    np.testing.assert_array_equal(C, res.matrix)
    assert C.shape == (len(jobs), len(pols))


def test_dedup_groups():
    """C1 x C2 x B collapses: every beta >= beta_0 shares Dealloc(beta_0)."""
    from repro.engine import build_grid_plan

    jobs, _ = _setup(n=8)
    grid = selfowned_policies()          # 175 policies
    gplan = build_grid_plan(jobs, grid, r_total=300)
    assert gplan.n_policies == 175
    # 13 distinct (Dealloc param, beta_0) pairs x 5 bids.
    assert len(gplan.groups) == 65
    covered = np.concatenate([g.policy_idx for g in gplan.groups])
    assert sorted(covered.tolist()) == list(range(175))


def test_replay_adapter_roundtrip():
    """A replayed price trace reproduces the source market's evaluation."""
    jobs, m = _setup()
    replay = replay_scenarios([m.price])[0]
    a = evaluate_grid(jobs, _grid(), m, backend="numpy")
    b = evaluate_grid(jobs, _grid(), replay, backend="numpy")
    np.testing.assert_array_equal(a.matrix, b.matrix)


def test_run_tola_scenarios_batches():
    """Scenario-batched TOLA: scenario 0 equals the plain single-market run."""
    jobs, m = _setup(n=40, jt=2)
    pols = spot_od_policies()[:8]
    markets = make_scenarios(m.horizon, 2, seed=33)
    batch = run_tola_scenarios(jobs, pols, markets, seed=3,
                               backend="numpy")
    solo = run_tola_scenarios(jobs, pols, [markets[0]], seed=3,
                              backend="numpy")[0]
    assert len(batch) == 2
    np.testing.assert_array_equal(batch[0].cost_matrix, solo.cost_matrix)
    np.testing.assert_array_equal(batch[0].chosen, solo.chosen)
    assert batch[0].average_unit_cost() == solo.average_unit_cost()


def test_backend_resolution():
    assert "numpy" in available_backends()
    assert resolve_backend("numpy") == "numpy"
    assert resolve_backend("auto") in ("numpy", "jax", "pallas")
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_auto_backend_surfaces_jax_platform_errors(monkeypatch):
    """"auto" resolves by the platform jax reports; a jax that cannot
    initialise its platform is an error, never a silent switch to numpy."""
    jax = pytest.importorskip("jax")

    def broken():
        raise RuntimeError("no platform could be initialised")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="no platform"):
        resolve_backend("auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto") == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_backend("auto") == "numpy"


def test_scenarios_must_share_grid():
    jobs, m = _setup()
    bad = SpotMarket(m.horizon + 50, seed=1)
    with pytest.raises(ValueError):
        evaluate_grid(jobs, _grid(), [m, bad], backend="numpy")


def test_engine_result_accessors():
    jobs, m = _setup()
    pols = _grid()
    res = evaluate_grid(jobs, pols, m, r_total=30, backend="numpy")
    p, alpha = res.best()
    assert alpha == res.avg_unit_cost()[0].min()
    sc = res.stream_costs(p, 0)
    assert abs(sc.average_unit_cost() - alpha) < 1e-12
    # work conservation: spot + on-demand + self-owned == workload
    total = (res.spot_work[0, :, p] + res.ondemand_work[0, :, p]
             + res.selfowned_work[:, p])
    np.testing.assert_allclose(total, res.workload, rtol=1e-9)


def test_available_backends_probes_pallas(monkeypatch):
    """"pallas" is advertised only when jax.experimental.pallas actually
    imports — a jax build without it must fail at SELECTION time with a
    message naming the missing piece, not mid-run."""
    import sys

    pytest.importorskip("jax")
    # Poison the pallas module: `import jax.experimental.pallas` now raises
    # ImportError even though `import jax` still succeeds.
    monkeypatch.setitem(sys.modules, "jax.experimental.pallas", None)
    avail = available_backends()
    assert "jax" in avail and "pallas" not in avail
    with pytest.raises(ValueError, match="jax.experimental.pallas"):
        resolve_backend("pallas")
    monkeypatch.undo()
    assert "pallas" in available_backends()
    assert resolve_backend("pallas") == "pallas"


def test_resolve_backend_env_override_validated(monkeypatch):
    """An invalid REPRO_ENGINE_BACKEND value is reported as the ENV problem
    it is (naming the variable), instead of blaming the caller's "auto"."""
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_ENGINE_BACKEND"):
        resolve_backend("auto")
    # explicit backends bypass the env override entirely
    assert resolve_backend("numpy") == "numpy"
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "numpy")
    assert resolve_backend("auto") == "numpy"
