"""Cross-call reuse layer (DESIGN.md §11): plan/view cache parity and
invalidation, the 12-decimal bid-key contract across calls, bounded
eviction, incremental (delta) grid evaluation, and the warm-path
zero-compile guarantee."""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    ChainJob,
    SpotMarket,
    Task,
    generate_chain_jobs,
    selfowned_policies,
)
from repro.core.scheduler import JobArrays, job_arrays
from repro.engine import (
    available_backends,
    evaluate_grid,
    evaluate_grid_delta,
    make_scenarios,
)
from repro.engine import cache
from repro.obs import METRICS

BACKENDS = [b for b in ("numpy", "jax", "pallas") if b in available_backends()]


@pytest.fixture(autouse=True)
def _fresh_caches():
    """Every test counts cache events from zero and leaves the global
    caches the way it found them (other test modules share them)."""
    prev = cache._ENABLED_OVERRIDE
    cache.clear_caches()
    cache.configure(enabled=True, plan_maxsize=1024, view_maxsize=128)
    yield
    cache.clear_caches()
    cache._ENABLED_OVERRIDE = prev
    cache.configure(plan_maxsize=1024, view_maxsize=128)


def _setup(n=16, jt=1, seed=3, scenarios=2):
    jobs = generate_chain_jobs(n, job_type=jt, seed=seed)
    horizon = max(j.deadline for j in jobs) + 1.0
    return jobs, make_scenarios(horizon, scenarios, seed=seed + 100)


def _grid(n=10):
    return selfowned_policies()[:n]


def _tensors(res):
    return (res.unit_cost, res.spot_cost, res.ondemand_cost,
            res.selfowned_work)


def _assert_bitwise(a, b):
    for x, y in zip(_tensors(a), _tensors(b)):
        assert np.array_equal(x, y)


# The paper-table configurations (exp1-4 shapes): dedicated/shared pool,
# dealloc/even windows, chain and planned-start editions, r=0 and r>0.
CONFIGS = [
    dict(r_total=0),
    dict(r_total=600),
    dict(r_total=600, windows="even", selfowned="naive", pool="shared"),
    dict(r_total=600, early_start=False),
]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("cfg", CONFIGS,
                         ids=["r0", "r600", "shared-even", "planned"])
def test_cache_on_off_parity_bitwise(backend, cfg):
    """Cold, warm (all groups from cache) and cache-off runs of the same
    grid are BITWISE identical on every backend: the cache returns the
    exact tensors the builder would have produced."""
    jobs, markets = _setup(jt=2 if cfg.get("early_start") is False else 1)
    kw = dict(cfg, backend=backend)
    cold = evaluate_grid(jobs, _grid(), markets, **kw)
    assert cold.timings["plan_cached"] == 0
    warm = evaluate_grid(jobs, _grid(), markets, **kw)
    assert warm.timings["plan_cached"] > 0
    assert warm.timings["plan_cached"] == len(cache.PLAN_CACHE)
    with cache.disabled():
        off = evaluate_grid(jobs, _grid(), markets, **kw)
        assert off.timings["plan_cached"] == 0
    _assert_bitwise(cold, warm)
    _assert_bitwise(cold, off)


def test_bid_collision_cross_call_bitwise():
    """Two bids differing in the 13th decimal hit the SAME cache entry
    across calls (the in-grid dedup already rounds bids to 12 decimals;
    the cross-call key must not be finer) and score bitwise-identically."""
    jobs, markets = _setup()
    p = _grid(1)[0]
    base = evaluate_grid(jobs, [p], markets, 600, backend="numpy")
    h0 = cache.PLAN_CACHE.cache_info().hits
    q = dataclasses.replace(p, bid=p.bid + 1e-13)
    assert q.bid != p.bid            # genuinely different floats...
    res = evaluate_grid(jobs, [q], markets, 600, backend="numpy")
    assert cache.PLAN_CACHE.cache_info().hits == h0 + 1  # ...same entry
    assert res.timings["plan_cached"] == 1
    _assert_bitwise(base, res)


def test_eviction_under_bound_rebuilds_identical():
    """A plan cache too small for the grid keeps evicting, but evicted
    groups rebuild to bitwise-identical tensors on the next call."""
    jobs, markets = _setup()
    grid = _grid(10)
    ref = evaluate_grid(jobs, grid, markets, 600, backend="numpy")
    n_groups = len(set(cache.PLAN_CACHE._data)) or 1
    cache.clear_caches()
    cache.configure(plan_maxsize=max(n_groups // 2, 1))
    a = evaluate_grid(jobs, grid, markets, 600, backend="numpy")
    b = evaluate_grid(jobs, grid, markets, 600, backend="numpy")
    info = cache.PLAN_CACHE.cache_info()
    assert cache.PLAN_CACHE.evictions > 0
    assert info.currsize <= info.maxsize
    _assert_bitwise(ref, a)
    _assert_bitwise(ref, b)


def test_resize_evicts_and_counts():
    lru = cache._LRU(4)
    for i in range(4):
        lru.put(i, i)
    lru.resize(2)
    assert len(lru) == 2 and lru.evictions == 2
    assert 3 in lru and 0 not in lru


def _perturbed(grid, every=4):
    out = list(grid)
    idx = list(range(0, len(grid), every))
    for k, i in enumerate(idx):
        out[i] = dataclasses.replace(grid[i],
                                     bid=grid[i].bid * 1.01 + 1e-4 * (k + 1))
    return out, len(idx)


@pytest.mark.parametrize("backend", BACKENDS)
def test_delta_matches_full(backend):
    """evaluate_grid_delta over a partially re-bid grid re-scores ONLY the
    changed groups and matches the full re-eval — bitwise on the numpy
    oracle, <=1e-5 on the f32 backends."""
    jobs, markets = _setup()
    grid = _grid(12)
    prev = evaluate_grid(jobs, grid, markets, 600, backend=backend)
    assert prev.delta_state is not None
    grid2, n_changed = _perturbed(grid)
    with METRICS.collecting(reset=True):
        delta = evaluate_grid_delta(prev, jobs, grid2, markets, 600,
                                    backend=backend)
        snap = METRICS.snapshot()
    full = evaluate_grid(jobs, grid2, markets, 600, backend=backend)
    rescored = delta.timings["delta_groups_rescored"]
    assert 0 < rescored <= n_changed
    assert rescored < delta.timings["delta_groups_total"]
    series = snap["engine.delta_groups_rescored"]["series"]
    assert series and series[0]["value"] == rescored
    if backend == "numpy":
        _assert_bitwise(delta, full)
    else:
        for x, y in zip(_tensors(delta), _tensors(full)):
            np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-5)
    # the chained state supports a second round of edits
    assert delta.delta_state is not None
    grid3, _ = _perturbed(grid2, every=6)
    again = evaluate_grid_delta(delta, jobs, grid3, markets, 600,
                                backend=backend)
    full3 = evaluate_grid(jobs, grid3, markets, 600, backend=backend)
    if backend == "numpy":
        _assert_bitwise(again, full3)


def test_delta_no_change_rescoring_zero():
    jobs, markets = _setup()
    grid = _grid(6)
    prev = evaluate_grid(jobs, grid, markets, 600, backend="numpy")
    same = evaluate_grid_delta(prev, jobs, grid, markets, 600,
                               backend="numpy")
    assert same.timings["delta_groups_rescored"] == 0
    _assert_bitwise(prev, same)


def test_delta_validation_names_the_mismatch():
    jobs, markets = _setup()
    grid = _grid(4)
    prev = evaluate_grid(jobs, grid, markets, 600, backend="numpy")
    other_jobs, _ = _setup(seed=9)
    with pytest.raises(ValueError, match="jobs"):
        evaluate_grid_delta(prev, other_jobs, grid, markets, 600,
                            backend="numpy")
    _, other_markets = _setup(seed=9)
    with pytest.raises(ValueError, match="scenario"):
        evaluate_grid_delta(prev, jobs, grid, other_markets, 600,
                            backend="numpy")
    with pytest.raises(ValueError, match="r_total|config"):
        evaluate_grid_delta(prev, jobs, grid, markets, 300,
                            backend="numpy")
    mean = evaluate_grid(jobs, grid, markets, 600, backend="numpy",
                         reduce="mean")
    assert mean.delta_state is None
    with pytest.raises(ValueError, match="delta_state"):
        evaluate_grid_delta(mean, jobs, grid, markets, 600,
                            backend="numpy")


def test_availability_queries_not_cached():
    """Availability-query plans (TOLA pool refinement) bypass the cache
    entirely — their tensors depend on realized pool state."""
    jobs, markets = _setup()
    m = markets[0]
    grid = _grid(4)
    q = lambda s0, e0: np.maximum(40.0 - s0, 0.0)
    res = evaluate_grid(jobs, grid, m, 600, backend="numpy",
                        availability=q)
    assert res.timings["plan_cached"] == 0
    assert len(cache.PLAN_CACHE) == 0
    assert res.delta_state is None


@pytest.mark.skipif("jax" not in BACKENDS, reason="needs jax")
def test_warm_call_compiles_nothing():
    """Second identical evaluate_grid call in one process triggers ZERO
    XLA backend compiles (the cache-smoke CI gate, via jax.monitoring)."""
    from repro.obs.compiled import CompileWatch

    jobs, markets = _setup()
    grid = _grid(8)
    kw = dict(backend="jax")
    evaluate_grid(jobs, grid, markets, 600, **kw)   # cold: compiles freely
    watch = CompileWatch()
    assert watch.supported
    with watch:
        res = evaluate_grid(jobs, grid, markets, 600, **kw)
    assert watch.compiles == 0
    assert res.timings["plan_cached"] > 0


def test_factory_caches_reports_bounds_and_evictions():
    from repro.obs.compiled import factory_caches

    jobs, markets = _setup()
    evaluate_grid(jobs, _grid(4), markets, 600, backend="numpy")
    caches = factory_caches()
    for name in ("engine.plan_cache", "engine.view_cache"):
        assert name in caches
        entry = caches[name]
        assert set(entry) == {"hits", "misses", "maxsize", "currsize",
                              "evictions"}
        assert entry["maxsize"] is not None
    assert caches["engine.plan_cache"]["misses"] > 0


def test_plan_cache_metrics_series():
    jobs, markets = _setup()
    grid = _grid(6)
    with METRICS.collecting(reset=True):
        evaluate_grid(jobs, grid, markets, 600, backend="numpy")
        evaluate_grid(jobs, grid, markets, 600, backend="numpy")
        snap = METRICS.snapshot()
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["engine.plan_cache"]["series"]}
    assert series[(("event", "miss"),)] > 0
    assert series[(("event", "hit"),)] == series[(("event", "miss"),)]


def test_jobs_fingerprint_invalidates():
    jobs, markets = _setup()
    res1 = evaluate_grid(jobs, _grid(4), markets, 600, backend="numpy")
    h0 = cache.PLAN_CACHE.cache_info()
    jobs2, _ = _setup(seed=11)
    res2 = evaluate_grid(jobs2, _grid(4), markets, 600, backend="numpy")
    h1 = cache.PLAN_CACHE.cache_info()
    assert res2.timings["plan_cached"] == 0       # different jobs: all miss
    assert h1.hits == h0.hits
    assert cache.jobs_fingerprint(jobs) != cache.jobs_fingerprint(jobs2)


def _with_task(job, k, **fields):
    tasks = list(job.tasks)
    tasks[k] = dataclasses.replace(tasks[k], **fields)
    return dataclasses.replace(job, tasks=tuple(tasks))


def _edit(jobs, ji, fn):
    jobs = list(jobs)
    jobs[ji] = fn(jobs[ji])
    return jobs


# One edit each to a quantity the plan reads, on a job of 7 tasks.
JOB_EDITS = {
    "deadline": lambda jobs: _edit(
        jobs, 2, lambda j: dataclasses.replace(j, deadline=j.deadline + 5.0)),
    "task_z": lambda jobs: _edit(
        jobs, 2, lambda j: _with_task(j, 1, z=j.tasks[1].z * 0.5)),
    "task_delta": lambda jobs: _edit(
        jobs, 2, lambda j: _with_task(j, 1, delta=j.tasks[1].delta * 2)),
    "job_order": lambda jobs: jobs[:2] + [jobs[3], jobs[2]] + jobs[4:],
    "task_count": lambda jobs: _edit(
        jobs, 2, lambda j: dataclasses.replace(j, tasks=j.tasks[:-1])),
}

WINDOW_CFGS = {
    "dealloc": dict(r_total=600),
    "even": dict(r_total=600, windows="even", selfowned="naive",
                 pool="shared"),
}


@pytest.mark.parametrize("windows", list(WINDOW_CFGS))
@pytest.mark.parametrize("edit", list(JOB_EDITS))
def test_fingerprint_covers_every_plan_input(edit, windows):
    """Any one edit to a job quantity the plan reads changes the key: the
    next call misses every group and equals a cache-off run bitwise."""
    jobs, markets = _setup()
    kw = dict(WINDOW_CFGS[windows], backend="numpy")
    evaluate_grid(jobs, _grid(), markets, **kw)
    edited = JOB_EDITS[edit](jobs)
    assert cache.jobs_fingerprint(edited) != cache.jobs_fingerprint(jobs)
    misses = cache.PLAN_CACHE.cache_info().misses
    warm = evaluate_grid(edited, _grid(), markets, **kw)
    assert warm.timings["plan_cached"] == 0
    assert (cache.PLAN_CACHE.cache_info().misses - misses
            == len(warm.delta_state["group_rep"]))
    with cache.disabled():
        off = evaluate_grid(edited, _grid(), markets, **kw)
    _assert_bitwise(warm, off)


@pytest.mark.parametrize("windows", list(WINDOW_CFGS))
def test_fingerprint_rebuilt_equal_jobs_hit(windows):
    """New ChainJob/Task objects with equal content key the same: the call
    on them hits every group and returns the same tensors bitwise."""
    jobs, markets = _setup()
    kw = dict(WINDOW_CFGS[windows], backend="numpy")
    cold = evaluate_grid(jobs, _grid(), markets, **kw)
    rebuilt = [ChainJob(j.arrival, j.deadline,
                        tuple(Task(t.z, t.delta) for t in j.tasks))
               for j in jobs]
    assert all(a is not b for a, b in zip(jobs, rebuilt))
    assert cache.jobs_fingerprint(rebuilt) == cache.jobs_fingerprint(jobs)
    misses = cache.PLAN_CACHE.cache_info().misses
    warm = evaluate_grid(rebuilt, _grid(), markets, **kw)
    assert cache.PLAN_CACHE.cache_info().misses == misses
    assert warm.timings["plan_cached"] == len(cache.PLAN_CACHE)
    _assert_bitwise(cold, warm)


def test_fingerprint_never_walks_job_objects(monkeypatch):
    """The key is the arrays' bytes: it never reprs a job or a task, and
    the ``jobs`` back-reference does not enter it."""
    jobs, _ = _setup()
    arrays = job_arrays(jobs)

    def _no_repr(self):
        raise AssertionError("the fingerprint walked a job object")

    monkeypatch.setattr(ChainJob, "__repr__", _no_repr)
    monkeypatch.setattr(Task, "__repr__", _no_repr)
    fp = cache.fingerprint_job_arrays(arrays)
    assert fp == cache.fingerprint_job_arrays(
        dataclasses.replace(arrays, jobs=None))
    assert fp == cache.jobs_fingerprint(jobs)


def test_fingerprint_hashes_shapes():
    """Equal bytes under another shape key differently."""
    jobs, _ = _setup()
    arrays = job_arrays(jobs)
    J, L = arrays.z.shape
    assert J != L
    reshaped = dataclasses.replace(arrays, z=arrays.z.reshape(L, J))
    assert reshaped.z.tobytes() == arrays.z.tobytes()
    assert (cache.fingerprint_job_arrays(reshaped)
            != cache.fingerprint_job_arrays(arrays))


@pytest.mark.parametrize(
    "field", [f.name for f in dataclasses.fields(JobArrays) if f.name != "jobs"])
def test_fingerprint_hashes_every_array_field(field):
    """One element changed in any one array field changes the key."""
    jobs, _ = _setup()
    arrays = job_arrays(jobs)
    v = getattr(arrays, field).copy()
    v.flat[0] = not v.flat[0] if v.dtype == bool else v.flat[0] + 1
    edited = dataclasses.replace(arrays, **{field: v})
    assert (cache.fingerprint_job_arrays(edited)
            != cache.fingerprint_job_arrays(arrays))


def test_scenario_fingerprint_kinds():
    jobs, markets = _setup()
    assert cache.scenario_fingerprint(markets) is not None
    assert (cache.scenario_fingerprint(markets)
            == cache.scenario_fingerprint(list(markets)))
    single = markets[0]
    assert cache.scenario_fingerprint(single) is not None
    assert (cache.scenario_fingerprint(single)
            != cache.scenario_fingerprint(markets))
    from repro.engine import ScenarioSpec
    spec = ScenarioSpec("fresh", 100.0, 4, seed=1)
    assert cache.scenario_fingerprint(spec) == spec


# --------------------------------------------------------------------------
# Persistent compilation cache placement (fresh processes: jax reads its
# cache settings once per process)
# --------------------------------------------------------------------------

_CACHE_PROBE = r"""
import json
import jax
import jax.numpy as jnp
from repro.engine import setup_persistent_cache
path = setup_persistent_cache()
jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_persistent_cache_directory(tmp_path, env_set):
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    env = {"PYTHONPATH": str(repo / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", ""),
           "HOME": os.environ.get("HOME", "")}
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if env_set else str(repo / ".jax_cache")
    assert got["path"] == got["config"] == want
    if env_set:
        # the compiled program landed in the caller's directory
        assert any(tmp_path.iterdir())
