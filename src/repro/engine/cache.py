"""Cross-call reuse layer of the evaluation engine (DESIGN.md §11).

The production workload is *many near-identical grids over time*: the
paper's parametric policy family is re-scored continually as the market
moves, so successive ``evaluate_grid`` calls share most of their
(Dealloc param, beta_0, bid) evaluation groups, their scenario views and
their compiled programs. This module makes the repeated call the fast
path:

* ``PLAN_CACHE`` — cross-call LRU of built ``EvalGroup`` records, keyed on
  the SAME dedup signature the plan layer uses within one grid
  (window key, rounded beta_0, ``round(bid, 12)``) plus the jobs
  fingerprint, pool configuration, and — when a ``GridMesh`` is in play —
  the mesh's (data, model) shard partition, so a warm hit only ever hands
  back buffers built for the identical sharding and stays bitwise.
  ``plan.build_grid_plan`` consults it per *group*, so a second call with
  an overlapping grid rebuilds only the new groups (and a
  fully-overlapping one rebuilds nothing).
* ``VIEW_CACHE`` — cross-call LRU of stacked scenario views keyed on
  (spec, chunk range, device, ``round(bid, 12)``); the per-batch memo in
  ``scenarios.ScenarioBatch.stacked`` dies with the batch, this one
  survives across ``evaluate_grid`` / ``replay_stream`` invocations.
  Feedback-driven (adaptive) chunks and meshed batches bypass it by
  construction — their views depend on state outside the key.
* ``evaluate_grid_delta`` — incremental evaluation: diff the new policy
  grid against the group signatures recorded on a previous
  ``EngineResult`` and re-score ONLY the new/changed groups, splicing the
  cached cost columns for the rest (bitwise-equal to a full re-eval on the
  numpy oracle; the scored groups are independent cells by construction).
* ``setup_persistent_cache`` — wires jax's persistent compilation cache so
  warm-DISK restarts skip XLA compiles too (used by ``launch/serve.py``
  and the benchmark harness; never enabled implicitly — a cold-vs-warm
  benchmark must stay honest).

Keys never hold raw floats that the plan layer would round: the bid enters
every key through ``plan._bid_key`` (``round(bid, 12)``), so two bids
differing below 1e-12 hit the SAME entry bitwise — the cross-call twin of
the PR 4 in-grid dedup rule.

``REPRO_ENGINE_CACHE=0`` (or ``configure(enabled=False)`` /
``disabled()``) turns the cross-call caches off; cache-on and cache-off
results are bitwise-identical per backend (tests/test_cache.py).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import os

import numpy as np

from repro.obs import METRICS, maybe_snapshot

__all__ = [
    "PLAN_CACHE", "VIEW_CACHE", "enabled", "configure", "disabled",
    "clear_caches", "jobs_fingerprint", "scenario_fingerprint",
    "evaluate_grid_delta", "setup_persistent_cache",
]

_CacheInfo = collections.namedtuple(
    "CacheInfo", ["hits", "misses", "maxsize", "currsize"])


class _LRU:
    """Bounded insertion/recency-ordered cache with eviction stats.

    Exposes ``cache_info()`` with the ``functools.lru_cache`` field layout
    (plus an ``evictions`` attribute) so ``obs.compiled.factory_caches``
    can report it through the same duck-typed hook as the jit factory
    caches. When ``metric`` is set, evictions emit
    ``<metric>{event=evict}`` through ``obs.METRICS``.
    """

    def __init__(self, maxsize: int, metric: str | None = None):
        self.maxsize = int(maxsize)
        self.metric = metric
        self._data: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, default=None):
        try:
            value = self._data[key]
        except KeyError:
            self.misses += 1
            return default
        self._data.move_to_end(key)
        self.hits += 1
        return value

    def put(self, key, value) -> None:
        if self.maxsize <= 0:
            return
        self._data[key] = value
        self._data.move_to_end(key)
        while len(self._data) > self.maxsize:
            self._data.popitem(last=False)
            self.evictions += 1
            if self.metric and METRICS.enabled:
                METRICS.counter(self.metric).inc(event="evict")

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data

    def cache_info(self) -> _CacheInfo:
        return _CacheInfo(self.hits, self.misses, self.maxsize,
                          len(self._data))

    def clear(self) -> None:
        """Drop entries AND counters — a cleared cache reports like a
        fresh one (tests rely on counting from zero)."""
        self._data.clear()
        self.hits = self.misses = self.evictions = 0

    def resize(self, maxsize: int) -> None:
        self.maxsize = int(maxsize)
        while len(self._data) > self.maxsize:
            self._data.popitem(last=False)
            self.evictions += 1


# A worst-case EvalGroup at J=512 is a few hundred KB of plan/pool
# tensors; 1024 entries bound the plan cache to a few hundred MB while
# covering many concurrent policy grids. Stacked views are
# (chunk, L)-sized per bid; 128 chunk-range entries cover a steady-state
# serving loop replaying the same spec windows.
PLAN_CACHE = _LRU(1024, metric="engine.plan_cache")
VIEW_CACHE = _LRU(128, metric="engine.view_cache")

_ENABLED_OVERRIDE: bool | None = None


def enabled() -> bool:
    """Cross-call caching on? ``configure(enabled=...)`` wins over the
    ``REPRO_ENGINE_CACHE`` environment toggle (``0`` disables)."""
    if _ENABLED_OVERRIDE is not None:
        return _ENABLED_OVERRIDE
    return os.environ.get("REPRO_ENGINE_CACHE", "1") != "0"


def configure(enabled: bool | None = None, plan_maxsize: int | None = None,
              view_maxsize: int | None = None) -> None:
    """Adjust the cross-call cache layer in-process.

    ``enabled=None`` leaves the current toggle; maxsize changes evict LRU
    entries immediately (counted as evictions).
    """
    global _ENABLED_OVERRIDE
    if enabled is not None:
        _ENABLED_OVERRIDE = bool(enabled)
    if plan_maxsize is not None:
        PLAN_CACHE.resize(plan_maxsize)
    if view_maxsize is not None:
        VIEW_CACHE.resize(view_maxsize)


@contextlib.contextmanager
def disabled():
    """Scoped cache-off (the cache-on/off parity tests run their oracle
    leg under this)."""
    global _ENABLED_OVERRIDE
    prev = _ENABLED_OVERRIDE
    _ENABLED_OVERRIDE = False
    try:
        yield
    finally:
        _ENABLED_OVERRIDE = prev


def clear_caches() -> None:
    """Drop every cross-call entry (plan groups and scenario views)."""
    PLAN_CACHE.clear()
    VIEW_CACHE.clear()


def plan_cache_events(hits: int = 0, misses: int = 0) -> None:
    """Emit the plan-cache hit/miss counters (one labeled series,
    DESIGN.md §11; evictions are emitted by the cache itself)."""
    if not METRICS.enabled or not (hits or misses):
        return
    c = METRICS.counter("engine.plan_cache")
    if hits:
        c.inc(float(hits), event="hit")
    if misses:
        c.inc(float(misses), event="miss")


# --------------------------------------------------------------------------
# Fingerprints: the invalidation half of the cache key contract.
# --------------------------------------------------------------------------

def _hash_arrays(h, arrays) -> None:
    for f in dataclasses.fields(arrays):
        # ``jobs`` is the source list the arrays were read from, not a plan
        # input: its repr walks every Task (MBs per call at §6.1 sizes).
        if f.name == "jobs":
            continue
        v = getattr(arrays, f.name)
        h.update(f.name.encode())
        if isinstance(v, np.ndarray):
            h.update(f"{v.dtype}{v.shape}".encode())
            h.update(np.ascontiguousarray(v).tobytes())
        else:
            h.update(repr(v).encode())


def fingerprint_job_arrays(arrays) -> str:
    """Content hash of a ``JobArrays`` batch — every field the plan layer
    reads, so any change to the job set invalidates its cache entries.

    The key is the arrays' dtypes, shapes and bytes; the ``jobs``
    back-reference is skipped. ``arrival``, ``window``, ``z``, ``delta``,
    ``mask`` and ``l`` determine both slacks (``omega`` and
    ``slack_even``), so equal keys give bitwise-equal plans in either
    window mode."""
    h = hashlib.sha1()
    _hash_arrays(h, arrays)
    return h.hexdigest()


def jobs_fingerprint(jobs) -> str:
    """Content hash of a job list (via its canonical array form)."""
    from repro.core.scheduler import job_arrays

    return fingerprint_job_arrays(job_arrays(jobs))


def scenario_fingerprint(scenarios):
    """Hashable identity of a scenario input, or None when it has none.

    A ``ScenarioSpec`` is its own fingerprint (frozen dataclass — equal
    specs synthesize equal markets). Materialized markets hash their price
    paths and slot grid. Reactive/adaptive streams return None: their
    chunks depend on feedback, so no cross-call identity exists and delta
    evaluation refuses them.
    """
    from repro.core.market import SpotMarket
    from repro.engine.scenarios import ScenarioSpec

    if isinstance(scenarios, ScenarioSpec):
        return scenarios
    if isinstance(scenarios, SpotMarket):
        scenarios = [scenarios]
    if isinstance(scenarios, (list, tuple)) and scenarios \
            and all(isinstance(m, SpotMarket) for m in scenarios):
        h = hashlib.sha1()
        for m in scenarios:
            h.update(np.ascontiguousarray(m.price, np.float64).tobytes())
            h.update(np.float64(m.slot).tobytes())
            h.update(np.int64(m.slots_per_unit).tobytes())
            h.update(np.float64(m.p_ondemand).tobytes())
        return h.hexdigest()
    return None


# --------------------------------------------------------------------------
# Incremental (delta) grid evaluation.
# --------------------------------------------------------------------------

def evaluate_grid_delta(prev, jobs, policies, scenarios, r_total: int = 0, *,
                        windows: str = "dealloc", selfowned: str = "prop12",
                        early_start: bool = True, pool: str = "dedicated",
                        backend: str | None = None,
                        plan_backend: str | None = None,
                        scenario_chunk: int | None = None,
                        mesh=None, overlap: bool | None = None):
    """Re-evaluate a policy grid incrementally against a previous result.

    Diffs the new grid's evaluation groups (the plan layer's
    (window key, beta_0, ``round(bid, 12)``) dedup signature) against the
    groups recorded on ``prev.delta_state``, re-scores ONLY the new/changed
    groups through :func:`repro.engine.evaluate_grid`, and splices the
    unchanged cost columns straight out of ``prev``'s tensors. The result
    is bitwise-equal to a full re-eval on the numpy oracle (each group is
    an independent evaluation cell) and float-level (<=1e-5) on jax/pallas.

    ``prev`` must come from a ``reduce="stack"`` ``evaluate_grid`` call
    over the SAME jobs, scenarios and pool configuration (validated via
    the fingerprints on ``prev.delta_state``; mismatches raise naming the
    offending input). The number of re-scored groups is emitted as the
    ``engine.delta_groups_rescored`` counter and returned in
    ``timings["delta_groups_rescored"]``.
    """
    from repro.engine.api import evaluate_grid
    from repro.engine.plan import _grid_structure
    from repro.engine.result import EngineResult

    st = getattr(prev, "delta_state", None)
    if st is None:
        raise ValueError(
            "prev carries no delta_state: delta evaluation needs a "
            "reduce='stack' evaluate_grid result over a fingerprintable "
            "scenario input (ScenarioSpec or materialized markets) with "
            "availability=None")
    cfg = st["config"]
    mismatches = [
        f"{name}: prev {cfg[name]!r} vs call {got!r}"
        for name, got in (("r_total", float(r_total)), ("windows", windows),
                          ("selfowned", selfowned), ("pool", pool),
                          ("early_start", bool(early_start)))
        if cfg[name] != got]
    if mismatches:
        raise ValueError(
            "delta evaluation config differs from prev's; re-scoring only "
            "changed groups would be wrong for: " + "; ".join(mismatches))
    if jobs_fingerprint(jobs) != st["jobs_fp"]:
        raise ValueError(
            "jobs changed since prev was computed (fingerprint mismatch); "
            "every group depends on the job set — run a full evaluate_grid")
    sfp = scenario_fingerprint(scenarios)
    if sfp is None or sfp != st["scenario_fp"]:
        raise ValueError(
            "scenarios changed since prev was computed (or are not "
            "fingerprintable); every group depends on the market "
            "realizations — run a full evaluate_grid")
    backend = cfg["backend"] if backend is None else backend
    plan_backend = cfg["plan_backend"] if plan_backend is None else \
        plan_backend

    policies = list(policies)
    s = _grid_structure(policies, r_total, windows)
    n_groups = len(s.g_bid)
    rep = st["group_rep"]
    changed = [gi for gi in range(n_groups) if s.g_key[gi] not in rep]

    S = prev.n_scenarios_total
    J = prev.unit_cost.shape[1]
    P = len(policies)
    keys = ("spot_cost", "ondemand_cost", "spot_work", "ondemand_work")
    out = {k: np.zeros((S, J, P)) for k in keys}
    so_work = np.zeros((J, P))
    so_res = np.zeros((J, P))

    for gi in range(n_groups):
        key = s.g_key[gi]
        if key not in rep:
            continue
        col = rep[key]
        pols = s.g_pols[gi]
        for k in keys:
            out[k][:, :, pols] = getattr(prev, k)[:, :, col][:, :, None]
        so_work[:, pols] = prev.selfowned_work[:, col][:, None]
        so_res[:, pols] = prev.selfowned_reserved[:, col][:, None]

    timings = {"delta_groups_rescored": len(changed),
               "delta_groups_total": n_groups}
    if changed:
        # One representative policy per changed group: the group tensors
        # depend on the policy only through its dedup signature, so the
        # representative's columns are every member's columns.
        rep_pols = [policies[s.g_pols[gi][0]] for gi in changed]
        inner = evaluate_grid(
            jobs, rep_pols, scenarios, r_total, windows=windows,
            selfowned=selfowned, early_start=early_start, pool=pool,
            backend=backend, plan_backend=plan_backend,
            scenario_chunk=scenario_chunk, reduce="stack", mesh=mesh,
            overlap=overlap)
        for i, gi in enumerate(changed):
            pols = s.g_pols[gi]
            for k in keys:
                out[k][:, :, pols] = getattr(inner, k)[:, :, i][:, :, None]
            so_work[:, pols] = inner.selfowned_work[:, i][:, None]
            so_res[:, pols] = inner.selfowned_reserved[:, i][:, None]
        backend = inner.backend
        for k in ("plan", "pool", "eval", "synth"):
            timings[k] = inner.timings.get(k, 0.0)
        timings["plan_cached"] = inner.timings.get("plan_cached", 0)
    if METRICS.enabled:
        METRICS.counter("engine.delta_groups_rescored").inc(
            float(len(changed)))

    workload = prev.workload.copy()
    total = out["spot_cost"] + out["ondemand_cost"]
    unit = total / np.maximum(workload, 1e-12)[None, :, None]
    return EngineResult(
        unit_cost=unit,
        spot_cost=out["spot_cost"],
        ondemand_cost=out["ondemand_cost"],
        spot_work=out["spot_work"],
        ondemand_work=out["ondemand_work"],
        workload=workload,
        selfowned_work=so_work,
        selfowned_reserved=so_res,
        backend=backend,
        single_market=prev.single_market,
        n_scenarios_total=S,
        timings=timings,
        obs=maybe_snapshot(),
        delta_state={
            "jobs_fp": st["jobs_fp"],
            "scenario_fp": st["scenario_fp"],
            "n_scenarios": S,
            "config": dict(cfg, backend=backend,
                           plan_backend=plan_backend),
            "group_rep": {s.g_key[gi]: int(s.g_pols[gi][0])
                          for gi in range(n_groups)},
        },
    )


# --------------------------------------------------------------------------
# Persistent (warm-disk) XLA compilation cache.
# --------------------------------------------------------------------------

# One fixed directory inside the checkout: the cache key includes the
# directory, so a path that moves between runs never hits.
_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def setup_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and no
    other directory is set here; otherwise the cache lives at
    ``.jax_cache`` in the checkout. Thresholds are lowered so even the
    small programs of the test grids persist.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
