"""Online-learning subsystem: numpy-vs-scan-vs-pallas replay parity, Hedge
bit-compatibility with the legacy in-module TOLA loop, seed determinism of the
sampled trace across backends, weight-underflow robustness on long
horizons, the Prop. B.1 regret-bound scaling, and the adversarial scenario
family."""

import numpy as np
import pytest

from repro.core import (
    Policy,
    SpotMarket,
    generate_chain_jobs,
    run_tola_scenarios,
    spot_od_policies,
)
from repro.learn import (
    LEARNER_KINDS,
    LearnerSpec,
    Schedule,
    build_events,
    prop_b1_bound,
    replay,
)

TOL = 1e-5
ALL_SPECS = [LearnerSpec(k) for k in LEARNER_KINDS]


def _tensor(S=2, n=45, m=7, seed=0, spread=0.4):
    """Synthetic (S, n, m) unit-cost tensor + Poisson-ish arrivals."""
    rng = np.random.default_rng(seed)
    C = rng.random((S, n, m)) * (1 - spread) + np.linspace(
        0, spread, m)[None, None, :]
    arrivals = np.cumsum(rng.exponential(0.25, n))
    d = 3.0
    Z = rng.random(n) + 0.5
    return C, arrivals, d, Z


def test_numpy_vs_scan_parity_every_learner():
    """The jax scan replay matches the float64 oracle for every learner:
    identical sampled traces, weights/probabilities within 1e-5."""
    C, arrivals, d, Z = _tensor()
    a = replay(C, arrivals, d, workload=Z, learners=ALL_SPECS, seed=3,
               backend="numpy")
    b = replay(C, arrivals, d, workload=Z, learners=ALL_SPECS, seed=3,
               backend="jax")
    np.testing.assert_array_equal(a.chosen, b.chosen)
    np.testing.assert_allclose(a.weights, b.weights, atol=TOL)
    np.testing.assert_allclose(a.p_chosen, b.p_chosen, atol=TOL)
    np.testing.assert_allclose(a.expected_unit, b.expected_unit, atol=TOL)
    np.testing.assert_allclose(a.regret_curve(), b.regret_curve(), atol=TOL)


def test_replay_accepts_device_tensor():
    """A jax cost tensor feeds the compiled scan directly (no f64 staging
    copy) and yields the same replay as the equivalent numpy input; the
    result container still hands back host float64."""
    jnp = pytest.importorskip("jax.numpy")
    C, arrivals, d, Z = _tensor()
    host = replay(C, arrivals, d, workload=Z, learners=["hedge"], seed=3,
                  backend="jax")
    dev = replay(jnp.asarray(C), arrivals, d, workload=Z,
                 learners=["hedge"], seed=3, backend="jax")
    np.testing.assert_array_equal(host.chosen, dev.chosen)
    np.testing.assert_allclose(host.weights, dev.weights, atol=TOL)
    assert isinstance(dev.unit_cost, np.ndarray)
    assert dev.unit_cost.dtype == np.float64
    # the numpy oracle transparently pulls a device tensor to host
    oracle = replay(jnp.asarray(C), arrivals, d, workload=Z,
                    learners=["hedge"], seed=3, backend="numpy")
    np.testing.assert_array_equal(oracle.chosen, host.chosen)


def test_pallas_kernel_parity_hedge():
    """The fused weight-update kernel (interpret mode on CPU) matches the
    oracle, including across an eta schedule grid."""
    C, arrivals, d, Z = _tensor(n=60, m=9, seed=1)
    specs = [LearnerSpec("hedge"),
             LearnerSpec("hedge", eta=Schedule("const", 0.3)),
             LearnerSpec("hedge", eta=Schedule("invsqrt", 0.5))]
    a = replay(C, arrivals, d, workload=Z, learners=specs, seed=5,
               backend="numpy")
    b = replay(C, arrivals, d, workload=Z, learners=specs, seed=5,
               backend="pallas")
    np.testing.assert_array_equal(a.chosen, b.chosen)
    np.testing.assert_allclose(a.weights, b.weights, atol=TOL)
    np.testing.assert_allclose(a.p_chosen, b.p_chosen, atol=TOL)


def test_pallas_hedge_ring_wraps_on_long_stream():
    """A stream much longer than its feedback delay keeps only a ring of
    trajectory rows in VMEM; the wrapped ring must replay exactly like the
    oracle."""
    from repro.kernels.weight_update import ring_rows

    rng = np.random.default_rng(3)
    n, m = 1500, 7
    C = rng.random((1, n, m))
    arrivals = np.cumsum(rng.exponential(0.25, n))
    d = 2.0
    _, _, n_done = build_events(arrivals, d)
    assert ring_rows(n_done, 128) < n   # the ring really wraps
    a = replay(C, arrivals, d, learners=["hedge"], seed=4, backend="numpy")
    b = replay(C, arrivals, d, learners=["hedge"], seed=4, backend="pallas")
    np.testing.assert_array_equal(a.chosen, b.chosen)
    np.testing.assert_allclose(a.weights, b.weights, atol=TOL)
    np.testing.assert_allclose(a.p_chosen, b.p_chosen, atol=TOL)


def test_hedge_replay_ref_matches_oracle():
    """kernels/ref.py's loop-free trajectory formulation == the sequential
    event loop (structurally different algorithms, same numbers)."""
    from repro.kernels.ref import hedge_replay_ref

    C, arrivals, d, _ = _tensor(S=1, seed=2)
    _, _, n_done = build_events(arrivals, d)
    etas = Schedule().values(arrivals, d, C.shape[-1])
    u = np.random.default_rng(9).random(len(arrivals))
    ref = hedge_replay_ref(C[0], etas, u, n_done)
    a = replay(C, arrivals, d, learners=["hedge"], seed=9, backend="numpy")
    np.testing.assert_array_equal(ref["chosen"], a.chosen[0, 0])
    np.testing.assert_allclose(ref["weights"], a.weights[0, 0], atol=1e-12)
    np.testing.assert_allclose(ref["p_chosen"], a.p_chosen[0, 0], atol=1e-12)


def test_seed_determinism_across_backends():
    """One seed -> ONE sampled-policy trace, whichever backend replays it
    (the uniform stream is drawn once in numpy and shared)."""
    C, arrivals, d, _ = _tensor(S=2, n=50, m=6, seed=4)
    outs = [replay(C, arrivals, d, learners=ALL_SPECS, seed=11, backend=bk)
            for bk in ("numpy", "jax", "pallas")]
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0].chosen, other.chosen)
    # and the same call repeated is bitwise identical
    again = replay(C, arrivals, d, learners=ALL_SPECS, seed=11,
                   backend="numpy")
    np.testing.assert_array_equal(outs[0].chosen, again.chosen)
    np.testing.assert_array_equal(outs[0].weights, again.weights)


def test_hedge_bit_compatible_with_legacy_loop():
    """run_tola_scenarios delegates to repro.learn and must reproduce the
    ORIGINAL in-module event loop draw for draw (rng.choice consumption
    included)."""
    jobs = generate_chain_jobs(60, job_type=2, seed=3)
    market = SpotMarket(max(j.deadline for j in jobs) + 1, seed=4)
    grid = spot_od_policies()[:8]
    res = run_tola_scenarios(jobs, grid, [market], seed=7,
                             backend="numpy")[0]

    # The pre-subsystem Algorithm 4 loop, verbatim.
    from repro.engine import evaluate_grid

    C = evaluate_grid(jobs, grid, market, backend="numpy").matrix
    arrivals = np.array([j.arrival for j in jobs])
    n, m = C.shape
    d = max(j.deadline - j.arrival for j in jobs)
    rng = np.random.default_rng(7)
    logw = np.full(m, -np.log(m))
    chosen = np.zeros(n, dtype=np.int64)
    events = sorted([(arrivals[j], 0, j) for j in range(n)]
                    + [(arrivals[j] + d, 1, j) for j in range(n)])
    for t, kind, j in events:
        if kind == 0:
            w = np.exp(logw - logw.max())
            w /= w.sum()
            chosen[j] = rng.choice(m, p=w)
        else:
            eta = np.sqrt(2.0 * np.log(m) / (d * max(t - d, d)))
            logw = logw - eta * C[j]
            logw -= logw.max()
    final_w = np.exp(logw - logw.max())
    final_w /= final_w.sum()

    np.testing.assert_array_equal(res.chosen, chosen)
    np.testing.assert_array_equal(res.weights, final_w)


def test_hedge_no_underflow_long_horizon():
    """Log-space renormalization regression: a 5k-job stream with losses
    biased against most policies must keep the weights finite and summing
    to one in every backend (naive w *= exp(-eta c) flushes to all-zero)."""
    rng = np.random.default_rng(0)
    n, m = 5000, 12
    C = rng.random((1, n, m)) * 0.2 + np.linspace(0, 0.8, m)[None, None, :]
    arrivals = np.cumsum(rng.exponential(0.25, n))
    spec = LearnerSpec("hedge", eta=Schedule("const", 0.5))
    for backend in ("numpy", "jax"):
        lr = replay(C, arrivals, 3.0, learners=[spec], seed=0,
                    backend=backend)
        w = lr.weights[0, 0]
        assert np.all(np.isfinite(w)), backend
        assert abs(w.sum() - 1.0) < 1e-5, backend
        assert w.max() > 1e-3, backend  # mass survived somewhere
        # and the learner actually concentrated on the cheap policies
        assert lr.chosen[0, 0][-100:].mean() < m / 4


@pytest.mark.parametrize("seed", range(4))
def test_hedge_regret_respects_prop_b1_scaling(seed):
    """Property: expected (sampling-noise-free) Hedge regret on synthetic
    cost matrices stays within the Prop. B.1-style delayed-feedback bound."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(80, 300))
    m = int(rng.integers(3, 25))
    C = rng.random((1, n, m))
    arrivals = np.cumsum(rng.exponential(float(rng.uniform(0.1, 0.6)), n))
    d = float(rng.uniform(0.5, 4.0))
    lr = replay(C, arrivals, d, learners=["hedge"], seed=seed,
                backend="numpy")
    total_regret = float(lr.regret_per_job(expected=True)[0, 0]) * n
    bound = prop_b1_bound(arrivals, d, m, c_max=1.0)
    assert total_regret <= bound, (total_regret, bound)


def test_prop_b1_bound_scaling_shape():
    """The bound itself scales like sqrt(n log m) at fixed delay."""
    arr = np.arange(400) * 0.25
    b1 = prop_b1_bound(arr[:100], 1.0, 8)
    b2 = prop_b1_bound(arr, 1.0, 8)
    assert 1.5 < b2 / b1 < 2.5  # sqrt(4x jobs) ~ 2x


def test_bandit_learners_only_see_sampled_column():
    """Feedback-model check: corrupting every UNSAMPLED cost entry after
    the fact cannot change a bandit learner's trajectory, but must change a
    full-information learner's."""
    C, arrivals, d, _ = _tensor(S=1, n=60, m=6, seed=6)
    base = replay(C, arrivals, d, learners=["exp3", "hedge"], seed=2,
                  backend="numpy")
    # corrupt: double every cost EXCEPT the entries exp3 actually sampled
    C2 = C * 2.0
    ch = base.chosen[0, 0]
    C2[0, np.arange(C.shape[1]), ch] = C[0, np.arange(C.shape[1]), ch]
    again = replay(C2, arrivals, d, learners=["exp3", "hedge"], seed=2,
                   backend="numpy")
    np.testing.assert_array_equal(base.chosen[0, 0], again.chosen[0, 0])
    np.testing.assert_allclose(base.weights[0, 0], again.weights[0, 0],
                               atol=1e-12)
    assert not np.array_equal(base.weights[0, 1], again.weights[0, 1])


def test_ftl_plays_cumulative_leader():
    C, arrivals, d, _ = _tensor(S=1, n=40, m=5, seed=8)
    lr = replay(C, arrivals, d, learners=["ftl"], seed=0, backend="numpy")
    _, _, n_done = build_events(arrivals, d)
    cum = np.concatenate([np.zeros((1, C.shape[2])),
                          np.cumsum(C[0], axis=0)])
    leaders = cum[n_done].argmin(axis=1)
    np.testing.assert_array_equal(lr.chosen[0, 0], leaders)


def test_learn_result_accessors():
    C, arrivals, d, Z = _tensor()
    lr = replay(C, arrivals, d, workload=Z, learners=ALL_SPECS, seed=1,
                backend="numpy")
    S, K, n = lr.chosen.shape
    assert (S, K) == (2, len(ALL_SPECS))
    curves = lr.regret_curve()
    assert curves.shape == (S, K, n)
    # the curve ends exactly at the headline per-job regret
    np.testing.assert_allclose(curves[..., -1], lr.regret_per_job(),
                               atol=1e-12)
    mean, lo, hi = lr.confidence_bands()
    assert mean.shape == (K, n)
    assert np.all(lo <= mean + 1e-12) and np.all(mean <= hi + 1e-12)
    assert len(lr.summary()) == K
    # fixed-policy accounting matches the tensor
    np.testing.assert_allclose(
        lr.fixed_unit_costs(),
        (C * Z[None, :, None]).sum(axis=1) / Z.sum(), atol=1e-12)


def test_adversarial_scenarios_share_grid_and_bite():
    """The adversarial family stacks with fresh scenarios (same slot grid)
    and drives realized unit costs strictly above the fresh-market level."""
    from repro.engine import evaluate_grid, make_scenarios

    jobs = generate_chain_jobs(40, job_type=2, seed=0)
    h = max(j.deadline for j in jobs) + 1
    adv = make_scenarios(h, 3, seed=5, kind="adversarial")
    fresh = make_scenarios(h, 3, seed=5, kind="fresh")
    assert adv[0].n_slots == fresh[0].n_slots
    # spikes sit at the on-demand ceiling, above every bid of the grid
    for m in adv:
        assert (m.price >= 0.999).mean() > 0.2
        assert m.beta_realized(0.30) < 0.8
    grid = spot_od_policies()[:10]
    res_a = evaluate_grid(jobs, grid, adv, backend="numpy")
    res_f = evaluate_grid(jobs, grid, fresh, backend="numpy")
    assert res_a.avg_unit_cost().mean() > res_f.avg_unit_cost().mean()


def test_run_tola_bandit_learner():
    """run_tola_scenarios accepts any learner kind; the realized bandit-TOLA stream
    stays within the on-demand unit-cost ceiling and carries its replay."""
    jobs = generate_chain_jobs(150, job_type=2, seed=11)
    market = SpotMarket(max(j.deadline for j in jobs) + 1, seed=12)
    grid = spot_od_policies()[:10]
    res = run_tola_scenarios(jobs, grid, [market], seed=0, backend="numpy",
                             learner="exp3")[0]
    assert res.learn is not None and res.learn.specs[0].kind == "exp3"
    assert 0.0 < res.average_unit_cost() <= market.p_ondemand + 1e-9
    # the counterfactual replay regret is consistent with the cost matrix
    r = res.learn.regret_per_job()[0, 0]
    assert np.isfinite(r)


@pytest.mark.slow
def test_learner_sweep_end_to_end():
    """Heavyweight: full learner x eta-grid sweep through the engine tensor
    across scenarios, jax vs numpy, with sane regret ordering."""
    from repro.engine import evaluate_grid, make_scenarios

    jobs = generate_chain_jobs(300, job_type=2, seed=1)
    h = max(j.deadline for j in jobs) + 1
    markets = make_scenarios(h, 3, seed=100, kind="fresh")
    grid = spot_od_policies()
    res = evaluate_grid(jobs, grid, markets, backend="numpy")
    arrivals = np.array([j.arrival for j in jobs])
    d = max(j.deadline - j.arrival for j in jobs)
    specs = [LearnerSpec(k) for k in LEARNER_KINDS] + [
        LearnerSpec("hedge", eta=Schedule("const", c)) for c in (0.05, 0.2)]
    a = replay(res, arrivals, d, learners=specs, seed=0, backend="numpy")
    b = replay(res, arrivals, d, learners=specs, seed=0, backend="jax")
    np.testing.assert_array_equal(a.chosen, b.chosen)
    np.testing.assert_allclose(a.weights, b.weights, atol=TOL)
    # full-information hedge should be no worse than uniform play
    uniform = a.fixed_unit_costs().mean(axis=1)
    hedge = a.realized_unit()[:, 0]
    assert (hedge <= uniform + 0.02).all()