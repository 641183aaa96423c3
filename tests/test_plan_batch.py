"""Vectorized plan layer: bit-compatibility of the batched builders with the
sequential Dealloc/plan loops, the jitted jax twin, the bid-stacked pallas
chain kernel, and the one-engine-pass-per-round TOLA refinement loop."""

import numpy as np
import pytest

from repro.core import (
    Policy,
    benchmark_bid_policies,
    generate_chain_jobs,
    selfowned_policies,
    spot_od_policies,
)
from repro.core.dealloc import (
    window_sizes,
    window_sizes_batch,
    window_sizes_batch_jax,
)
from repro.core.scheduler import build_plans, build_plans_batch, job_arrays
from repro.core.tola import run_tola_scenarios
from repro.engine import make_scenarios
from repro.engine.plan import distinct_window_params

PLAN_FIELDS = ("starts", "ends", "z", "delta", "mask", "arrival")


def _grid_params(policies, r_total):
    """Distinct Dealloc parameters of a policy grid (engine dedup order)."""
    return list(distinct_window_params(policies, r_total).values())


@pytest.mark.parametrize("job_type", [1, 2, 3, 4])
def test_batched_plans_bitwise_vs_loop(job_type):
    """build_plans_batch over the exp1-exp4 policy grids is BIT-identical to
    looping build_plans per distinct window parameter."""
    jobs = generate_chain_jobs(40, job_type, seed=10 + job_type)
    grid = spot_od_policies() + selfowned_policies()
    for r_total in (0, 300):
        xs = _grid_params(grid, r_total)
        batch = build_plans_batch(jobs, xs)
        assert len(batch) == len(xs)
        for bp, x in zip(batch, xs):
            loop = build_plans(jobs, Policy(beta=x, bid=0.27), r_total)
            for f in PLAN_FIELDS:
                np.testing.assert_array_equal(
                    getattr(bp, f), getattr(loop, f), err_msg=f)


def test_batched_even_plans_bitwise_vs_loop():
    """The Even-benchmark window mode (exp1/exp4 bench grids) matches too."""
    jobs = generate_chain_jobs(35, 2, seed=9)
    pol = benchmark_bid_policies()[0]
    (bp,) = build_plans_batch(jobs, windows="even")
    loop = build_plans(jobs, pol, 0, windows="even")
    for f in PLAN_FIELDS:
        np.testing.assert_array_equal(getattr(bp, f), getattr(loop, f),
                                      err_msg=f)


def test_window_sizes_batch_validates():
    jobs = generate_chain_jobs(5, 1, seed=1)
    a = job_arrays(jobs)
    with pytest.raises(ValueError):
        window_sizes_batch(a.e, a.delta, a.mask, a.omega, [0.0])
    with pytest.raises(ValueError):
        window_sizes_batch(a.e, a.delta, a.mask, a.omega, [1.5])
    with pytest.raises(ValueError):
        window_sizes_batch(a.e, a.delta, a.mask, a.omega - 1e3, [0.5])


def test_window_sizes_jax_twin_parity():
    """The jitted device twin agrees with the f64 canonical batch pass."""
    pytest.importorskip("jax")
    jobs = generate_chain_jobs(30, 3, seed=4)
    a = job_arrays(jobs)
    xs = np.array([0.3, 0.625, 1.0])
    want = window_sizes_batch(a.e, a.delta, a.mask, a.omega, xs)
    got = np.asarray(window_sizes_batch_jax(a.e, a.delta, a.mask,
                                            a.omega, xs))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and the canonical pass matches the scalar Algorithm 1 loop
    for g, x in enumerate(xs):
        for ji, job in enumerate(jobs):
            np.testing.assert_array_equal(want[g, ji, :job.l],
                                          window_sizes(job, float(x)))


def test_chain_kernel_bid_stacked_parity():
    """One bid-stacked launch == per-bid chain_costs_ref, incl. row padding
    and scenario-specific plans."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.kernels.policy_cost import policy_cost_chain
    from repro.kernels.ref import chain_costs_ref

    rng = np.random.default_rng(0)
    S, L = 2, 4
    rows_per_bid = [10, 7]          # un-equal -> exercises zero-padding
    bids = [0.18, 0.27]
    markets = make_scenarios(60.0, S, seed=5)
    R_max = max(rows_per_bid)
    B = len(bids)
    A = np.stack([np.stack([m.view(b).A_cum for m in markets])
                  for b in bids])
    C = np.stack([np.stack([m.view(b).C_cum for m in markets])
                  for b in bids])
    arrival = np.zeros((B, R_max))
    ends = np.zeros((B, R_max, L))
    z_t = np.zeros((B, S, R_max, L))
    d_eff = np.zeros((B, S, R_max, L))
    pins = np.zeros((B, S, R_max, L), dtype=bool)
    for bi, R in enumerate(rows_per_bid):
        arrival[bi, :R] = rng.uniform(0, 20, R)
        sizes = rng.uniform(0.2, 6, (R, L))
        ends[bi, :R] = arrival[bi, :R, None] + np.cumsum(sizes, axis=1)
        d = rng.choice([1.0, 8.0, 64.0], (S, R, L))
        z_t[bi, :, :R] = rng.uniform(0, 1, (S, R, L)) * d * sizes
        d_eff[bi, :, :R] = d
        pins[bi, :, :R] = rng.random((S, R, L)) < 0.15
    got = policy_cost_chain(A, C, arrival, ends, z_t, d_eff, pins,
                            interpret=True)
    for bi, R in enumerate(rows_per_bid):
        for s in range(S):
            ref = chain_costs_ref(
                jnp.asarray(A[bi, s], jnp.float32),
                jnp.asarray(C[bi, s], jnp.float32),
                jnp.asarray(arrival[bi, :R], jnp.float32),
                jnp.asarray(ends[bi, :R], jnp.float32),
                jnp.asarray(z_t[bi, s, :R], jnp.float32),
                jnp.asarray(d_eff[bi, s, :R], jnp.float32),
                jnp.asarray(pins[bi, s, :R]))
            for key in ("spot_cost", "ondemand_cost", "spot_work",
                        "ondemand_work"):
                np.testing.assert_allclose(
                    np.asarray(got[key])[bi, s, :R], np.asarray(ref[key]),
                    atol=3e-3, rtol=3e-3, err_msg=f"{key} bid {bi} s {s}")


def test_run_tola_scenarios_one_engine_pass_per_round(monkeypatch):
    """Refinement issues EXACTLY one evaluate_grid call per round, and the
    Table-6 outputs stay bit-identical to the sequential per-scenario path."""
    import repro.engine as engine_mod

    jobs = generate_chain_jobs(30, 2, seed=3)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=21)
    pols = selfowned_policies()[::25]
    pool_iters = 2

    calls = []
    real = engine_mod.evaluate_grid

    def counting(*args, **kwargs):
        calls.append(kwargs.get("availability"))
        return real(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "evaluate_grid", counting)
    batch = run_tola_scenarios(jobs, pols, markets, r_total=50, seed=7,
                               pool_iters=pool_iters, backend="numpy")
    monkeypatch.undo()
    # one call per round: the dedicated round 0 plus each refinement
    assert len(calls) == 1 + pool_iters
    assert calls[0] is None
    assert all(isinstance(a, list) and len(a) == len(markets)
               for a in calls[1:])

    for s, m in enumerate(markets):
        solo = run_tola_scenarios(jobs, pols, [m], r_total=50, seed=7 + s,
                                  pool_iters=pool_iters, backend="numpy")[0]
        np.testing.assert_array_equal(batch[s].cost_matrix, solo.cost_matrix)
        np.testing.assert_array_equal(batch[s].chosen, solo.chosen)
        np.testing.assert_array_equal(batch[s].weights, solo.weights)
        np.testing.assert_array_equal(batch[s].fixed_unit_costs,
                                      solo.fixed_unit_costs)
        np.testing.assert_array_equal(batch[s].realized.total_cost,
                                      solo.realized.total_cost)
        assert batch[s].average_unit_cost() == solo.average_unit_cost()


def test_per_scenario_availability_matches_per_scenario_calls():
    """engine: a list of S availability queries == S single-query passes."""
    from repro.engine import evaluate_grid

    jobs = generate_chain_jobs(25, 2, seed=6)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=11)
    pols = selfowned_policies()[::30]
    qs = [lambda s0, e0: np.full_like(s0, 13.0),
          lambda s0, e0: np.maximum(40.0 - s0, 0.0)]
    both = evaluate_grid(jobs, pols, markets, 60, availability=qs,
                         backend="numpy")
    assert both.selfowned_work.ndim == 3
    for s, m in enumerate(markets):
        alone = evaluate_grid(jobs, pols, m, 60, availability=qs[s],
                              backend="numpy")
        np.testing.assert_array_equal(both.unit_cost[s], alone.matrix)
        np.testing.assert_array_equal(both.selfowned_work[s],
                                      alone.selfowned_work)
    with pytest.raises(ValueError):
        evaluate_grid(jobs, pols, markets, 60, availability=qs[:1],
                      backend="numpy")


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("early_start", [True, False])
def test_per_scenario_availability_backend_parity(backend, early_start):
    """jax / pallas(interpret) agree with numpy on per-scenario-refined
    grids, for both the chain and the planned-start paths."""
    pytest.importorskip("jax")
    from repro.engine import evaluate_grid

    jobs = generate_chain_jobs(20, 2, seed=8)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=13)
    pols = selfowned_policies()[::40]
    qs = [lambda s0, e0: np.full_like(s0, 9.0),
          lambda s0, e0: np.maximum(30.0 - 0.5 * s0, 0.0)]
    kw = dict(availability=qs, early_start=early_start)
    if not early_start:
        kw.update(windows="even", selfowned="naive")
    ref = evaluate_grid(jobs, pols, markets, 50, backend="numpy", **kw)
    got = evaluate_grid(jobs, pols, markets, 50, backend=backend, **kw)
    np.testing.assert_allclose(got.unit_cost, ref.unit_cost,
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# window_sizes_batch knife edges (bit-identity with the sequential Alg.-1
# loop on the paths a generic random stream rarely exercises)
# ---------------------------------------------------------------------------

def _chain(arrival, deadline, zs, deltas):
    from repro.core.types import ChainJob, Task

    return ChainJob(arrival=arrival, deadline=deadline,
                    tasks=tuple(Task(z=z, delta=d)
                                for z, d in zip(zs, deltas)))


def _assert_batch_matches_loop(jobs, xs):
    a = job_arrays(jobs)
    got = window_sizes_batch(a.e, a.delta, a.mask, a.omega, xs)
    for g, x in enumerate(xs):
        for ji, job in enumerate(jobs):
            np.testing.assert_array_equal(
                got[g, ji, :job.l], window_sizes(job, float(x)),
                err_msg=f"x={x} job={ji}")
            assert np.all(got[g, ji, job.l:] == 0.0)  # padding takes none


def test_window_sizes_batch_single_task_residual():
    """Single-task jobs whose slack exceeds the cap: the overflow parks on
    the one task (order[0]) exactly like the sequential residual branch."""
    jobs = [_chain(0.0, 12.0, [4.0], [2.0]),      # e=2, cap(0.5)=2, slack 10
            _chain(3.0, 5.0, [1.5], [3.0]),       # e=0.5, slack 1.5
            _chain(1.0, 1.5, [0.5], [1.0])]       # zero slack single task
    _assert_batch_matches_loop(jobs, np.array([0.25, 0.5, 0.9]))


def test_window_sizes_batch_x_one_zero_cap():
    """x == 1.0: every cap is zero, so ALL slack is residual and parks on
    the max-delta task (ties broken by the stable sort, matching the loop)."""
    jobs = [_chain(0.0, 20.0, [2.0, 6.0, 1.0], [1.0, 4.0, 2.0]),
            # tie on delta: residual must land on the FIRST max-delta task
            _chain(2.0, 15.0, [3.0, 3.0, 2.0], [2.0, 2.0, 2.0]),
            _chain(0.0, 9.0, [4.0], [2.0])]
    _assert_batch_matches_loop(jobs, np.array([1.0]))
    # and mixed with x < 1 parameters in the same grid pass
    _assert_batch_matches_loop(jobs, np.array([0.5, 1.0, 0.8]))


def test_window_sizes_batch_all_slack_exhausted_break():
    """A grid where every job's slack is zero takes the early ``break`` (rem
    never populated) and must stay bit-identical to the sequential loop —
    all windows exactly the minimum execution times."""
    jobs = [_chain(0.0, 2.0, [2.0, 4.0], [2.0, 4.0]),     # window == e.sum()
            _chain(1.0, 3.5, [1.0, 3.0], [1.0, 2.0]),
            _chain(0.5, 2.0, [1.5, 3.0], [2.0, 4.0])]
    xs = np.array([0.3, 0.625, 1.0])
    a = job_arrays(jobs)
    assert np.all(a.omega == 0.0)
    got = window_sizes_batch(a.e, a.delta, a.mask, a.omega, xs)
    np.testing.assert_array_equal(
        got, np.broadcast_to(a.e, got.shape), err_msg="sizes must equal e")
    _assert_batch_matches_loop(jobs, xs)


# ---------------------------------------------------------------------------
# GridPlan bid dedup (rounded-key regression) + plan-layer availability check
# ---------------------------------------------------------------------------

def test_gridplan_bid_lookup_uses_rounded_key():
    """Bids differing at the 13th decimal collapse into ONE group, and
    groups_for_bid finds that group under EITHER raw float (regression:
    raw-float comparison silently returned [])."""
    from repro.engine.plan import build_grid_plan

    jobs = generate_chain_jobs(6, 2, seed=2)
    b1, b2 = 0.27, 0.27 + 1e-13
    assert b1 != b2                      # genuinely distinct floats
    pols = [Policy(beta=0.5, bid=b1), Policy(beta=0.5, bid=b2),
            Policy(beta=0.5, bid=0.3)]
    gplan = build_grid_plan(jobs, pols, r_total=0)
    assert len(gplan.groups) == 2
    assert len(gplan.bids) == 2
    g1 = gplan.groups_for_bid(b1)
    g2 = gplan.groups_for_bid(b2)
    assert g1 == g2 and len(g1) == 1
    assert sorted(g1[0].policy_idx.tolist()) == [0, 1]
    # every policy column is covered exactly once across bids
    covered = np.concatenate(
        [g.policy_idx for b in gplan.bids for g in gplan.groups_for_bid(b)])
    assert sorted(covered.tolist()) == [0, 1, 2]


def test_availability_length_checked_in_plan_layer():
    """A mismatched per-scenario availability list fails loudly inside
    build_grid_plan (not via a later backend shape error)."""
    from repro.engine.plan import build_grid_plan

    jobs = generate_chain_jobs(5, 1, seed=3)
    pols = selfowned_policies()[:2]
    q = lambda s0, e0: np.full_like(s0, 5.0)
    with pytest.raises(ValueError, match="one query per scenario"):
        build_grid_plan(jobs, pols, 40, availability=[q], n_scenarios=2)
    # without n_scenarios the caller opted out of the check (S' = len(list))
    gp = build_grid_plan(jobs, pols, 40, availability=[q, q])
    assert gp.per_scenario


# ---------------------------------------------------------------------------
# Device plan path: parity with the f64 canonical plan layer, hot-path
# device residency, and the jitted core twins
# ---------------------------------------------------------------------------

def test_expected_spot_work_jax_parity():
    pytest.importorskip("jax")
    from repro.core.dealloc import expected_spot_work, expected_spot_work_jax

    rng = np.random.default_rng(2)
    z = rng.uniform(0.1, 30.0, (40, 5))
    delta = rng.choice([1.0, 2.0, 8.0], (40, 5))
    sizes = z / delta + rng.uniform(0.0, 4.0, (40, 5))
    for x in (0.3, 0.625, 1.0):
        want = expected_spot_work(z, delta, sizes, x)
        got = np.asarray(expected_spot_work_jax(z, delta, sizes, x))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["prop12", "naive"])
def test_selfowned_counts_jax_parity(mode):
    """The jitted policy-(12) twin matches the f64 oracle exactly on generic
    (non-knife-edge) grids, including the NaN-beta0 convention."""
    pytest.importorskip("jax")
    from repro.core.scheduler import (
        _selfowned_counts_vec,
        selfowned_counts_vec_jax,
    )

    rng = np.random.default_rng(7)
    z = rng.uniform(0.3, 6.0, (30, 4))
    delta = rng.choice([1.0, 2.0, 4.0], (30, 4))
    sizes = rng.uniform(0.4, 3.0, (30, 4))
    beta0 = rng.choice([0.31, 0.57, np.nan], (30, 1))
    for avail in (7.0, rng.uniform(0.0, 5.0, (2, 30, 4))):
        want = _selfowned_counts_vec(z, delta, sizes, beta0, avail, mode)
        got = np.asarray(selfowned_counts_vec_jax(z, delta, sizes, beta0,
                                                  avail, mode=mode))
        if np.isscalar(avail):
            # integral counts (or the integral pool bound): exact match
            np.testing.assert_array_equal(got, want)
        else:
            # a continuous availability query can be the binding min —
            # then the result is the f32-rounded query value itself
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("job_type", [1, 2, 3, 4])
def test_device_plan_parity_exp_grids(job_type):
    """evaluate_grid with the device plan path matches the f64 canonical
    (host plan + numpy oracle) to <=1e-5 over the exp1-exp4 workloads."""
    pytest.importorskip("jax")
    from repro.engine import evaluate_grid

    jobs = generate_chain_jobs(30, job_type, seed=5 + job_type)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=7)
    grid = spot_od_policies() + selfowned_policies()[::7]
    ref = evaluate_grid(jobs, grid, markets, 60, backend="numpy")
    dev = evaluate_grid(jobs, grid, markets, 60, backend="jax",
                        plan_backend="device")
    assert dev.timings["plan_device"] > 0.0
    np.testing.assert_allclose(dev.unit_cost, ref.unit_cost,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dev.selfowned_work, ref.selfowned_work,
                               atol=1e-2, rtol=1e-4)


def test_device_plan_hot_path_never_calls_host_plan_layer(monkeypatch):
    """backend="jax" (plan_backend auto -> device) must not touch the host
    f64 plan builders: window_sizes_batch, build_plans_batch and the host
    policy-(12) counts are all stubbed out to fail loudly."""
    pytest.importorskip("jax")
    import sys

    import repro.core.scheduler as sched_mod
    import repro.engine.plan as plan_mod
    from repro.engine import evaluate_grid

    # repro.core re-exports a `dealloc` FUNCTION that shadows the submodule
    # attribute, so fetch the module object itself.
    dealloc_mod = sys.modules["repro.core.dealloc"]

    jobs = generate_chain_jobs(12, 2, seed=4)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=9)
    pols = selfowned_policies()[::40]

    def boom(*a, **k):
        raise AssertionError("host plan layer called on the device path")

    monkeypatch.setattr(plan_mod, "build_plans_batch", boom)
    monkeypatch.setattr(plan_mod, "_selfowned_counts_vec", boom)
    monkeypatch.setattr(sched_mod, "window_sizes_batch", boom)
    monkeypatch.setattr(dealloc_mod, "window_sizes_batch", boom)
    res = evaluate_grid(jobs, pols, markets, 50, backend="jax")
    assert res.timings["plan_device"] > 0.0
    monkeypatch.undo()
    ref = evaluate_grid(jobs, pols, markets, 50, backend="numpy")
    np.testing.assert_allclose(res.unit_cost, ref.unit_cost,
                               atol=1e-5, rtol=1e-5)


def test_device_plan_single_availability_query_parity():
    """The staged device path (host availability callables between the two
    jit stages) matches the host plan for a single shared query."""
    pytest.importorskip("jax")
    from repro.engine import evaluate_grid

    jobs = generate_chain_jobs(18, 3, seed=6)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=11)
    pols = selfowned_policies()[::30]
    q = lambda s0, e0: np.maximum(35.0 - 0.25 * s0, 0.0)
    ref = evaluate_grid(jobs, pols, markets, 50, availability=q,
                        backend="numpy")
    dev = evaluate_grid(jobs, pols, markets, 50, availability=q,
                        backend="jax", plan_backend="device")
    assert dev.timings["pool"] > 0.0      # staged leg, not the fused one
    np.testing.assert_allclose(dev.unit_cost, ref.unit_cost,
                               atol=1e-5, rtol=1e-5)


def test_device_plan_pallas_backend_parity():
    """The pallas (interpret) backend consumes device plan tensors and
    agrees with the canonical path."""
    pytest.importorskip("jax")
    from repro.engine import evaluate_grid

    jobs = generate_chain_jobs(8, 2, seed=12)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=13)
    pols = selfowned_policies()[::60]
    ref = evaluate_grid(jobs, pols, markets, 40, backend="numpy")
    dev = evaluate_grid(jobs, pols, markets, 40, backend="pallas",
                        plan_backend="device")
    np.testing.assert_allclose(dev.unit_cost, ref.unit_cost,
                               atol=1e-5, rtol=1e-5)


def test_plan_backend_resolution():
    pytest.importorskip("jax")
    from repro.engine import evaluate_grid, resolve_plan_backend

    assert resolve_plan_backend("auto", "numpy") == "host"
    assert resolve_plan_backend("auto", "jax") == "device"
    assert resolve_plan_backend("auto", "pallas") == "device"
    assert resolve_plan_backend("auto", "jax", pool="shared") == "host"
    assert resolve_plan_backend("host", "numpy") == "host"
    with pytest.raises(ValueError, match="host-only"):
        resolve_plan_backend("device", "numpy")
    with pytest.raises(ValueError, match="shared"):
        resolve_plan_backend("device", "jax", pool="shared")
    with pytest.raises(ValueError, match="unknown plan backend"):
        resolve_plan_backend("tpu", "jax")

    jobs = generate_chain_jobs(4, 1, seed=1)
    m = make_scenarios(max(j.deadline for j in jobs) + 1, 1, seed=1)
    with pytest.raises(ValueError, match="host-only"):
        evaluate_grid(jobs, [Policy(beta=0.5, bid=0.2)], m,
                      backend="numpy", plan_backend="device")


def test_device_plan_naive_scalar_availability_parity():
    """Regression: the naive counts rule ignores the window sizes, so with a
    SCALAR availability its result used to drop the akey axis and the group
    gather sliced the wrong dimension (exp4's Even-benchmark leg)."""
    pytest.importorskip("jax")
    from repro.engine import evaluate_grid

    jobs = generate_chain_jobs(14, 2, seed=15)
    markets = make_scenarios(max(j.deadline for j in jobs) + 1, 2, seed=16)
    pols = [Policy(beta=0.5, bid=b, beta0=0.4) for b in (0.18, 0.27)]
    kw = dict(windows="even", selfowned="naive", early_start=False)
    ref = evaluate_grid(jobs, pols, markets, 40, backend="numpy", **kw)
    dev = evaluate_grid(jobs, pols, markets, 40, backend="jax",
                        plan_backend="device", **kw)
    np.testing.assert_allclose(dev.unit_cost, ref.unit_cost,
                               atol=1e-5, rtol=1e-5)
