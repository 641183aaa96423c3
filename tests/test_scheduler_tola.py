"""System behaviour: Algorithm 2 end-to-end, pool accounting, TOLA learning,
and the paper's headline claim (proposed < baselines)."""

import numpy as np
import pytest

from repro.core import (
    B_BIDS,
    Policy,
    SelfOwnedPool,
    SpotMarket,
    generate_chain_jobs,
    run_greedy,
    run_jobs,
    run_tola_scenarios,
    spot_od_policies,
)
from repro.core.pool import RangeMax
from repro.core.scheduler import evaluate_policy_fullpool


def _setup(n=120, jt=1, seed=3):
    jobs = generate_chain_jobs(n, job_type=jt, seed=seed)
    market = SpotMarket(max(j.deadline for j in jobs) + 1, seed=seed + 1)
    return jobs, market


def test_proposed_beats_baselines():
    """The paper's core claim at small scale: min-over-grid proposed cost
    undercuts Greedy and Even benchmarks."""
    jobs, m = _setup(150, jt=1)
    best = min(run_jobs(jobs, p, m).average_unit_cost()
               for p in spot_od_policies())
    greedy = min(run_greedy(jobs, b, m).average_unit_cost() for b in B_BIDS)
    even = min(run_jobs(jobs, p, m, windows="even",
                        early_start=False).average_unit_cost()
               for p in spot_od_policies())
    assert best < greedy
    assert best < even


def test_selfowned_reduces_cost_monotonically():
    jobs, m = _setup(80, jt=2)
    pol = Policy(beta=0.625, bid=0.27, beta0=0.5)
    alphas = [run_jobs(jobs, pol, m, r_total=r).average_unit_cost()
              for r in (0, 200, 600)]
    assert alphas[0] > alphas[1] > alphas[2]


def test_pool_never_oversubscribed():
    jobs, m = _setup(60, jt=2)
    pol = Policy(beta=0.625, bid=0.27, beta0=1 / 2.2)
    costs, r_alloc, pool = run_jobs(jobs, pol, m, r_total=50,
                                    return_pool=True)
    assert pool is not None
    assert pool.used.max() <= 50
    assert costs.selfowned_work.sum() <= pool.worked_instance_time + 1e-6


def test_deadlines_always_met():
    """No allocation path may ever miss a deadline (on-demand backstop)."""
    jobs, m = _setup(100, jt=1)
    for pol in (Policy(beta=0.455, bid=0.18), Policy(beta=1.0, bid=0.30)):
        c = run_jobs(jobs, pol, m)
        # all workload processed by one of the three classes
        total = c.spot_work + c.ondemand_work + c.selfowned_work
        np.testing.assert_allclose(total, c.workload, rtol=1e-9)


def test_fullpool_equals_realized_when_no_selfowned():
    jobs, m = _setup(50, jt=3)
    pol = Policy(beta=0.769, bid=0.24)
    a = run_jobs(jobs, pol, m)
    b = evaluate_policy_fullpool(jobs, pol, m)
    np.testing.assert_allclose(a.total_cost, b.total_cost, atol=1e-9)


def test_tola_learns_good_policy():
    """With enough jobs the weight mass should concentrate on policies whose
    fixed cost is near the best fixed cost."""
    jobs, m = _setup(400, jt=2, seed=11)
    grid = spot_od_policies()
    res = run_tola_scenarios(jobs, grid, [m], seed=0)[0]
    fixed = res.fixed_unit_costs
    # weight-weighted expected cost is better than the uniform average
    uniform = fixed.mean()
    weighted = float((res.weights * fixed).sum())
    assert weighted < uniform
    # realized cost is within the policy-grid range
    assert fixed.min() - 1e-9 <= res.average_unit_cost() <= fixed.max() + 0.05


def test_rangemax_matches_naive():
    rng = np.random.default_rng(0)
    v = rng.integers(0, 100, 500).astype(float)
    rm = RangeMax(v)
    lo = rng.integers(0, 499, 200)
    hi = lo + rng.integers(1, 80, 200)
    got = rm.query(lo, hi)
    want = np.array([v[l:h].max() if h <= 500 else v[l:500].max()
                     for l, h in zip(lo, np.minimum(hi, 500))])
    np.testing.assert_allclose(got, want)


def test_early_start_never_hurts():
    jobs, m = _setup(100, jt=1)
    pol = Policy(beta=0.625, bid=0.27)
    early = run_jobs(jobs, pol, m, early_start=True).average_unit_cost()
    planned = run_jobs(jobs, pol, m, early_start=False).average_unit_cost()
    assert early <= planned + 1e-9


def _allocate_pool_reference(plan, r_total, selfowned, spu):
    """The original one-task-at-a-time chronological allocation loop."""
    from repro.core.pool import SelfOwnedPool
    from repro.core.scheduler import _selfowned_counts_vec

    J, L = plan.z.shape
    r_alloc = np.zeros((J, L))
    if r_total <= 0:
        return r_alloc, None
    flat = np.nonzero(plan.mask.ravel())[0]
    starts = plan.starts.ravel()[flat]
    ends = plan.ends.ravel()[flat]
    zf = plan.z.ravel()[flat]
    df = plan.delta.ravel()[flat]
    b0f = np.repeat(plan.beta0, L)[flat]
    sizes = np.maximum(ends - starts, 1e-12)
    cap = _selfowned_counts_vec(zf, df, sizes, b0f, np.inf, selfowned)
    pool = SelfOwnedPool(r_total, max(float(ends.max()), 1.0), spu)
    out = np.zeros(len(flat))
    slot = pool.slot
    k1s = np.maximum(np.floor(starts / slot + 1e-9).astype(np.int64), 0)
    k2s = np.minimum(np.ceil(ends / slot - 1e-9).astype(np.int64),
                     pool.n_slots)
    k2s = np.maximum(k2s, k1s + 1)
    used, total = pool.used, pool.total
    for i in np.argsort(starts, kind="stable"):
        if cap[i] <= 0.0 or ends[i] - starts[i] <= 1e-12:
            continue
        k1, k2 = k1s[i], k2s[i]
        r = int(min(cap[i], total - used[k1:k2].max(initial=0)))
        if r > 0:
            used[k1:k2] += r
            span = ends[i] - starts[i]
            pool.reserved_instance_time += r * span
            pool.worked_instance_time += min(r * span, zf[i])
            out[i] = r
    r_alloc.ravel()[flat] = out
    return r_alloc, pool


@pytest.mark.parametrize("n,jt,r,so", [
    (120, 2, 600, "prop12"),   # saturated interior (paper regime)
    (120, 2, 15, "prop12"),    # tiny pool, contended from the start
    (150, 1, 40, "naive"),     # naive self-owned benchmark
    (80, 3, 2000, "prop12"),   # uncontended: pure batched-commit path
    (90, 4, 7, "naive"),
])
def test_allocate_pool_batched_equals_sequential(n, jt, r, so):
    """The chunked-optimistic allocation (batched occupancy writes +
    segment-tree contended passes) is EXACTLY the sequential scan."""
    from repro.core.scheduler import _allocate_pool, build_plans

    jobs, _ = _setup(n, jt=jt, seed=n + r)
    pol = Policy(beta=0.625, bid=0.27, beta0=0.5)
    plan = build_plans(jobs, pol, r)
    got_a, got_p = _allocate_pool(plan, r, so, 12)
    want_a, want_p = _allocate_pool_reference(plan, r, so, 12)
    np.testing.assert_array_equal(got_a, want_a)
    np.testing.assert_array_equal(got_p.used, want_p.used)
    assert abs(got_p.reserved_instance_time
               - want_p.reserved_instance_time) < 1e-6
    assert abs(got_p.worked_instance_time
               - want_p.worked_instance_time) < 1e-6


