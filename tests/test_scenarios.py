"""Scenario subsystem: declarative specs, device synthesis parity, chunked
streaming bit-identity, the adaptive adversary, and the satellite
contracts (view caching, validation, replay padding)."""

import warnings

import numpy as np
import pytest

from repro.core import SpotMarket, generate_chain_jobs, spot_od_policies
from repro.engine import (
    ScenarioSpec,
    ScenarioStream,
    as_source,
    check_scenarios,
    evaluate_grid,
    make_scenarios,
    replay_scenarios,
)
from repro.engine.scenarios import (
    MarketListBatch,
    SynthBatch,
    _avail_threshold,
    _levels,
)
from repro.learn import replay, replay_stream

TOL = 1e-5


def _setup(n=16, jt=1, seed=5):
    jobs = generate_chain_jobs(n, job_type=jt, seed=seed)
    return jobs, max(j.deadline for j in jobs) + 1.0


def _grid(n=8):
    return spot_od_policies()[:n]


# --------------------------------------------------------------------------
# ScenarioSpec basics
# --------------------------------------------------------------------------

def test_spec_hashable_and_validated():
    spec = ScenarioSpec("fresh", 20.0, 4, seed=3)
    assert {spec: 1}[ScenarioSpec("fresh", 20.0, 4, seed=3)] == 1
    assert spec != ScenarioSpec("fresh", 20.0, 4, seed=4)
    with pytest.raises(ValueError, match="kind"):
        ScenarioSpec("bogus", 20.0, 4)
    with pytest.raises(ValueError, match="scenario"):
        ScenarioSpec("fresh", 20.0, 0)
    with pytest.raises(ValueError, match="trace"):
        ScenarioSpec("replay", 20.0, 1)
    with pytest.raises(ValueError, match="replay"):
        ScenarioSpec("fresh", 20.0, 1, traces=((1.0,),))
    with pytest.raises(ValueError, match="2 traces"):
        ScenarioSpec("replay", 1.0, 3, traces=((1.0,), (0.5,)))


def test_make_scenarios_adaptive_needs_stream():
    with pytest.raises(ValueError, match="adaptive"):
        make_scenarios(20.0, 4, kind="adaptive")


def test_levels_bit_identical_numpy_vs_jax():
    jnp = pytest.importorskip("jax.numpy")
    idx = np.arange(5, 17)
    hn = _levels(99, 1, idx, 301)
    hj = np.asarray(_levels(99, 1, jnp.asarray(idx, jnp.int32), 301, xp=jnp))
    np.testing.assert_array_equal(hn, hj)
    assert hn.max() < 2 ** 24


@pytest.mark.parametrize("kind", ["fresh", "regime", "adversarial",
                                  "adaptive"])
def test_prices_chunk_slicing_and_materialize_bitwise(kind):
    """Any chunk reproduces the monolithic rows exactly, and materialize()
    wraps exactly those rows (today's from_prices path)."""
    spec = ScenarioSpec(kind, 15.0, 7, seed=11)
    P = spec.prices()
    np.testing.assert_array_equal(spec.prices(2, 6), P[2:6])
    np.testing.assert_array_equal(spec.prices(6, 7), P[6:7])
    mats = spec.materialize()
    np.testing.assert_array_equal(np.stack([m.price for m in mats]), P)
    assert all(m.n_slots == spec.n_slots for m in mats)


def test_avail_threshold_replicates_f64_comparison():
    """The device path's integer availability threshold selects EXACTLY the
    slots the host f64 ``price <= bid + 1e-12`` comparison selects."""
    rng = np.random.default_rng(0)
    for _ in range(40):
        mean = float(rng.uniform(0.05, 0.3))
        bid = float(rng.uniform(0.1, 0.45))
        t = _avail_threshold(mean, 0.12, 1.0, bid)
        hs = rng.integers(0, 2 ** 24, 4000)
        price = np.minimum(0.12 + mean * (-np.log1p(-(hs * 2.0 ** -24))),
                           1.0)
        np.testing.assert_array_equal(price <= bid + 1e-12, hs <= t)


# --------------------------------------------------------------------------
# Engine integration: spec paths vs the materialized list path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["fresh", "adversarial"])
def test_spec_numpy_bit_identical_to_materialized_list(kind):
    """numpy backend: spec (chunked or not) == materialized list, bitwise."""
    jobs, horizon = _setup()
    spec = ScenarioSpec(kind, horizon, 5, seed=9)
    ref = evaluate_grid(jobs, _grid(), spec.materialize(), 30,
                        backend="numpy")
    whole = evaluate_grid(jobs, _grid(), spec, 30, backend="numpy")
    chunked = evaluate_grid(jobs, _grid(), spec, 30, backend="numpy",
                            scenario_chunk=2)
    np.testing.assert_array_equal(whole.unit_cost, ref.unit_cost)
    np.testing.assert_array_equal(chunked.unit_cost, ref.unit_cost)
    assert len(chunked.timings["chunks"]) == 3


def test_chunked_list_path_bit_identical():
    """scenario_chunk=K == scenario_chunk=S == today's list path, bitwise."""
    jobs, horizon = _setup()
    markets = make_scenarios(horizon, 5, seed=21, kind="regime")
    ref = evaluate_grid(jobs, _grid(), markets, 30, backend="numpy")
    for k in (1, 2, 5):
        got = evaluate_grid(jobs, _grid(), markets, 30, backend="numpy",
                            scenario_chunk=k)
        np.testing.assert_array_equal(got.unit_cost, ref.unit_cost)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("kind", ["fresh", "regime", "adversarial"])
def test_spec_device_parity_all_backends(backend, kind):
    """Device-synthesized spec chunks vs the f64 numpy oracle — including
    the spiked (adversarial) grid — within the engine's 1e-5 contract."""
    jobs, horizon = _setup(n=10)
    spec = ScenarioSpec(kind, horizon, 4, seed=13)
    ref = evaluate_grid(jobs, _grid(6), spec, 20, backend="numpy")
    got = evaluate_grid(jobs, _grid(6), spec, 20, backend=backend,
                        scenario_chunk=2)
    np.testing.assert_allclose(got.unit_cost, ref.unit_cost,
                               atol=TOL, rtol=TOL)


def test_spec_jax_chunked_matches_monolithic():
    jobs, horizon = _setup(n=10)
    spec = ScenarioSpec("adversarial", horizon, 6, seed=3)
    whole = evaluate_grid(jobs, _grid(6), spec, 20, backend="jax")
    chunked = evaluate_grid(jobs, _grid(6), spec, 20, backend="jax",
                            scenario_chunk=2)
    np.testing.assert_allclose(chunked.unit_cost, whole.unit_cost,
                               atol=1e-7, rtol=1e-7)


def test_adaptive_spec_device_parity():
    """The adaptive family's streamed chunks (periods + pinned phases) agree
    across numpy and jax given identical adversary decisions."""
    pytest.importorskip("jax")
    jobs, horizon = _setup(n=10)
    spec = ScenarioSpec("adaptive", horizon, 4, seed=5, n_periods=2,
                        n_phases=2)
    periods = np.array([0.5, 0.5, 2.0, 2.0])
    offsets = np.array([0, 3, -1, 7])
    host = SynthBatch(spec, 0, 4, periods=periods, offsets=offsets,
                      device=False).prepare()
    dev = SynthBatch(spec, 0, 4, periods=periods, offsets=offsets,
                     device=True).prepare()
    for bid in (0.18, 0.30):
        Ah, Ch = host.stacked(bid)
        Ad, Cd = (np.asarray(x, np.float64) for x in dev.stacked(bid))
        # identical availability slot sets -> identical A steps
        np.testing.assert_array_equal(np.diff(Ah, axis=1) > 0,
                                      np.diff(Ad, axis=1) > 0)
        np.testing.assert_allclose(Cd, Ch, atol=1e-4)


def test_reduce_mean_matches_stacked_mean():
    jobs, horizon = _setup()
    spec = ScenarioSpec("fresh", horizon, 6, seed=2)
    ref = evaluate_grid(jobs, _grid(), spec, 30, backend="numpy")
    red = evaluate_grid(jobs, _grid(), spec, 30, backend="numpy",
                        scenario_chunk=2, reduce="mean")
    assert red.unit_cost.shape[0] == 1
    assert red.n_scenarios_total == 6
    np.testing.assert_allclose(red.unit_cost[0], ref.unit_cost.mean(axis=0),
                               rtol=1e-12)
    with pytest.raises(ValueError, match="reduce"):
        evaluate_grid(jobs, _grid(), spec, 30, backend="numpy",
                      reduce="median")


# --------------------------------------------------------------------------
# Satellites: validation, view caching, replay padding
# --------------------------------------------------------------------------

def test_check_scenarios_empty_is_clear_value_error():
    with pytest.raises(ValueError, match="at least one"):
        check_scenarios([])
    jobs, _ = _setup(n=4)
    with pytest.raises(ValueError, match="at least one"):
        evaluate_grid(jobs, _grid(4), [], backend="numpy")


def test_scenario_chunk_validated_at_api_boundary():
    jobs, horizon = _setup(n=4)
    m = SpotMarket(horizon, seed=1)
    for bad in (0, -3, 2.5, True, "4"):
        with pytest.raises(ValueError, match="scenario_chunk"):
            evaluate_grid(jobs, _grid(4), m, backend="numpy",
                          scenario_chunk=bad)
    # chunking cannot split per-scenario availability batches
    markets = [SpotMarket(horizon, seed=s) for s in range(2)]
    queries = [lambda s, e: np.full(s.shape, 3.0)] * 2
    with pytest.raises(ValueError, match="per-scenario"):
        evaluate_grid(jobs, _grid(4), markets, 30, backend="numpy",
                      availability=queries, scenario_chunk=1)


def test_stacked_views_cached_no_recompute(monkeypatch):
    """The batch builds each bid's stacked views ONCE: repeated calls (and
    repeated engine passes over the same source) hand back the same arrays
    without touching SpotMarket.view again."""
    jobs, horizon = _setup(n=6)
    markets = make_scenarios(horizon, 3, seed=8)
    built = {"n": 0}
    orig = SpotMarket.view

    def counting_view(self, bid):
        if round(float(bid), 12) not in self._views:
            built["n"] += 1              # an actual view CONSTRUCTION
        return orig(self, bid)

    monkeypatch.setattr(SpotMarket, "view", counting_view)
    batch = MarketListBatch(markets)
    A1, C1 = batch.stacked(0.25)
    assert built["n"] == len(markets)
    A2, C2 = batch.stacked(0.25)
    assert A2 is A1 and C2 is C1
    assert built["n"] == len(markets)
    # same rounding rule as the GridPlan dedup: a 13th-decimal twin hits
    # the same cache entry (and constructs nothing)
    A3, _ = batch.stacked(0.25 + 1e-13)
    assert A3 is A1
    assert built["n"] == len(markets)

    # engine passes over one source never rebuild a (market, bid) view
    source = as_source(markets)
    built["n"] = 0
    evaluate_grid(jobs, _grid(4), source, backend="numpy")
    n_one_pass = built["n"]
    assert n_one_pass > 0
    evaluate_grid(jobs, _grid(4), source, backend="numpy")
    assert built["n"] == n_one_pass


def test_replay_padding_contract():
    """Short traces are right-padded with the documented above-every-bid
    price, a warning names the padding, and the padded scenario evaluates
    exactly like a manually padded market."""
    jobs, horizon = _setup(n=6)
    m = SpotMarket(horizon, seed=3)
    short = m.price[:m.n_slots // 2]
    with pytest.warns(UserWarning, match="1 trace"):
        markets = replay_scenarios([m.price, short])
    manual = np.concatenate([short, np.full(m.n_slots - len(short), 1.0)])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        np.testing.assert_array_equal(markets[1].price, manual)
        ref = evaluate_grid(jobs, _grid(4),
                            SpotMarket.from_prices(manual),
                            backend="numpy")
        got = evaluate_grid(jobs, _grid(4), markets[1], backend="numpy")
    np.testing.assert_array_equal(got.unit_cost, ref.unit_cost)
    # equal-length traces pad nothing and warn nothing
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        replay_scenarios([m.price, m.price * 0.5])
    # the declarative replay spec carries the same contract
    with pytest.warns(UserWarning, match="padded"):
        spec = ScenarioSpec.from_traces([m.price, short])
        np.testing.assert_array_equal(spec.prices()[1], manual)


# --------------------------------------------------------------------------
# Streamed learning + the adaptive adversary
# --------------------------------------------------------------------------

def test_replay_stream_matches_monolithic_replay():
    """Chunked replay_stream == replay over the materialized tensor (same
    seeds per scenario, summaries to float-summation tolerance)."""
    jobs, horizon = _setup(n=12, jt=2)
    grid = _grid(6)
    spec = ScenarioSpec("fresh", horizon, 6, seed=4)
    arrivals = np.array([j.arrival for j in jobs])
    d = max(j.deadline - j.arrival for j in jobs)
    Z = np.array([j.total_work for j in jobs])
    res = evaluate_grid(jobs, grid, spec.materialize(), 0, backend="numpy")
    lr = replay(res.unit_cost, arrivals, d, workload=Z,
                learners=["hedge", "exp3"], seed=0, backend="numpy")
    slr = replay_stream(jobs, grid, spec, 0, learners=["hedge", "exp3"],
                        seed=0, scenario_chunk=2, backend="numpy",
                        engine_backend="numpy")
    assert slr.n_scenarios == 6 and slr.n_chunks == 3
    np.testing.assert_allclose(slr.realized_unit(),
                               lr.realized_unit().mean(axis=0), rtol=1e-12)
    np.testing.assert_allclose(slr.regret_per_job(),
                               lr.regret_per_job().mean(axis=0),
                               rtol=1e-9, atol=1e-13)
    m_s, lo_s, hi_s = slr.confidence_bands()
    m_m, lo_m, hi_m = lr.confidence_bands()
    np.testing.assert_allclose(m_s, m_m, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(lo_s, lo_m, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(hi_s, hi_m, rtol=1e-6, atol=1e-9)
    for a, b in zip(slr.summary(), lr.summary()):
        assert a["learner"] == b["learner"]
        assert abs(a["regret"] - b["regret"]) < 1e-9


def test_adaptive_stream_stages_and_lock():
    """Stage machine: period sweep -> phase sweep at the worst period ->
    locked (period, phase); driven with synthetic feedback, no engine."""
    spec = ScenarioSpec("adaptive", 10.0, 12, seed=1, n_periods=2,
                        n_phases=3, spike_range=(0.5, 4.0))
    stream = ScenarioStream(spec)
    it = stream.chunks(4)
    next(it)
    assert stream.stage == "periods"
    # period 1 (4.0) hurts much more
    stream.observe(np.array([0.1, 0.9, 0.1, 0.9]))
    next(it)
    assert stream.stage == "phases"
    assert np.all(stream.chunk_periods[-1] == spec.period_menu()[1])
    assert len(np.unique(stream.chunk_offsets[-1])) == 3   # phase sweep
    # chunk 2 covers global indices 4..7 -> phase candidates [1, 2, 0, 1];
    # make candidate 2 (global index 5) hurt most
    stream.observe(np.array([0.5, 1.4, 0.6, 0.5]))
    next(it)
    assert stream.stage == "locked"
    cand = stream._phase_candidates(1)
    assert np.all(stream.chunk_offsets[-1] == cand[2])
    assert np.all(stream.chunk_periods[-1] == spec.period_menu()[1])


@pytest.mark.parametrize("engine_backend", ["numpy"])
def test_adaptive_adversary_beats_best_fixed_family(engine_backend):
    """ROADMAP adaptive-adversary regression: on the same scenario budget,
    the adaptive family's realized TOLA (hedge) regret must be >= every
    FIXED square-wave family's — it finds the worst period AND pins the
    phase, a lever the phase-randomized fixed families don't have.
    Deterministic: f64 numpy end to end, fixed seeds.
    """
    jobs = generate_chain_jobs(20, 2, seed=4)
    grid = spot_od_policies()[:10]
    horizon = max(j.deadline for j in jobs) + 1.0
    S, K = 48, 8
    kw = dict(learners=["hedge"], seed=0, backend="numpy",
              engine_backend=engine_backend)
    fixed = {}
    for p in (0.25, 8.0):
        spec_p = ScenarioSpec("adversarial", horizon, S, seed=7,
                              spike_range=(p, p))
        fixed[p] = float(replay_stream(jobs, grid, spec_p, 0,
                                       scenario_chunk=S, **kw)
                         .regret_per_job()[0])
    spec_a = ScenarioSpec("adaptive", horizon, S, seed=7,
                          spike_range=(0.25, 8.0), n_periods=2, n_phases=4)
    stream = ScenarioStream(spec_a)
    adaptive = float(replay_stream(jobs, grid, stream, 0, scenario_chunk=K,
                                   **kw).regret_per_job()[0])
    best_fixed = max(fixed.values())
    assert stream.stage == "locked"
    # locked onto the genuinely worst period of the menu
    assert stream._menu[stream._locked_period] == max(fixed, key=fixed.get)
    assert adaptive >= best_fixed, (
        f"adaptive adversary regret {adaptive:.4f} fell below the best "
        f"fixed square-wave family {best_fixed:.4f} ({fixed})")
