"""Plan layer of the evaluation engine.

Turns (jobs x policies) into a deduplicated batch of *evaluation groups*.
The key observation: the padded ``PlanBatch`` (the canonical interchange
type) depends on a policy only through its Dealloc parameter, the
self-owned allocation only through (plan, beta_0), and the market
realization additionally through the bid. Policies sharing the triple
(window key, beta_0, bid) are therefore EXACT duplicates of one another
and collapse into one group — the paper's C1 x C2 x B grid of 175 policies
reduces to 35 distinct evaluations because every beta >= beta_0 drives
Dealloc with beta_0 (Alg. 2 lines 1-5).

The plan layer is itself part of the array program, and it is
**backend-parametric** (``plan_backend``):

* ``"host"`` — float64 numpy, the bit-exact oracle: window plans for ALL
  distinct Dealloc parameters come out of ONE vectorized
  ``build_plans_batch`` pass (``core.dealloc.window_sizes_batch``,
  bit-identical to the legacy per-job loop), and the market-independent
  arithmetic (policy-(12) counts, cloud residuals, pins) follows in f64.
* ``"device"`` — the same pipeline as ONE fused jit program (device dtype,
  usually f32): the Alg.-1 waterfill (``core.dealloc`` jnp twin), the
  policy-(12) counts (``core.scheduler._selfowned_counts_impl``), the
  cloud residuals, and the group gather all trace into a single XLA
  computation whose outputs stay on device — the jax/pallas cost kernels
  consume them without a host staging copy. Parity with the host path is
  float-level (<=1e-5 relative on unit costs; tests/test_plan_batch.py),
  NOT bitwise, and integral-count ceils use a widened epsilon
  (``scheduler._DEVICE_CEIL_EPS``) to absorb f32 noise.

Every backend (numpy / jax / pallas) consumes the same ``GridPlan``
structure; the numpy oracle requires a host plan. When ``availability`` is
a *list* of per-scenario queries (TOLA's batched pool refinement), the
self-owned arrays gain a leading scenario axis — groups carry (S, J, L)
tensors and backends pair scenario s with slice s. Availability queries
are host callables, so the device path stages the planned windows to host
once to evaluate them (the default query-free path never leaves device).
Inside ``METRICS.collecting()`` the counter
``engine.plan.availability_windows`` counts the (start, end) windows a
refined plan sends to its availability queries, padding tasks included.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.obs import METRICS, record_jit, span

from repro.engine import cache as _cache
from repro.core.scheduler import (
    PlanBatch,
    Policy,
    _allocate_pool,
    _selfowned_counts_vec,
    build_plans_batch,
    job_arrays,
)
from repro.core.types import ChainJob

__all__ = ["EvalGroup", "GridPlan", "build_grid_plan", "scenario_cat",
           "concat_rows", "distinct_window_params", "OUT_KEYS"]

_PLAN_BACKENDS = ("host", "device")

# The per-cell cost decomposition every eval backend writes into ``out``.
OUT_KEYS = ("spot_cost", "ondemand_cost", "spot_work", "ondemand_work")

# Dust threshold of the DEVICE residual-workload kill. The host oracle
# zeroes residuals below 1e-9 * (z + 1) — the f64 cancellation floor of
# z - r * sizes. Device arithmetic is f32 whose cancellation noise is
# ~1e-7 relative, so the same subtraction leaves phantom residuals the
# 1e-9 threshold would keep alive; 1e-6 kills them. Genuine residuals are
# either 0 or substantial, so the widened window changes nothing real.
_DEVICE_DUST = 1e-6


def _xp_of(a):
    """numpy for host arrays, jax.numpy for device-resident arrays."""
    if isinstance(a, np.ndarray):
        return np
    import jax.numpy as jnp

    return jnp


def _query(q, starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """One availability query over planned windows, counted in
    ``engine.plan.availability_windows``."""
    if METRICS.enabled:
        METRICS.counter("engine.plan.availability_windows").inc(starts.size)
    return q(starts, ends)


def concat_rows(arrays):
    """Concatenate group row batches along axis 0 without forcing device
    tensors through host (np.concatenate on jax arrays would)."""
    return _xp_of(arrays[0]).concatenate(arrays)


def scenario_cat(groups, attr: str, S: int):
    """Concatenate a group attribute into an (S, R, L) scenario-major stack,
    broadcasting groups whose arrays are scenario-independent — the one
    place the per-scenario/shared mixing rule lives (both the jax and the
    pallas backend consume it). Device tensors stay on device."""
    xp = _xp_of(getattr(groups[0], attr))
    return xp.concatenate(
        [xp.broadcast_to(getattr(g, attr),
                         (S,) + tuple(g.plan.ends.shape)) for g in groups],
        axis=1)


def _bid_key(bid: float) -> float:
    """The one bid-comparison rule of the plan layer: groups are deduped,
    listed and looked up on the SAME rounded value (raw-float comparison
    would let two bids differing below 1e-12 collapse into one group and
    then miss it on lookup)."""
    return round(bid, 12)


@dataclasses.dataclass
class EvalGroup:
    """One distinct (window plan, beta_0, bid) evaluation cell.

    ``policy_idx`` lists every policy of the original grid that this group
    realizes. The self-owned arrays are (J, L) when market-independent and
    (S, J, L) when the caller supplied per-scenario availability queries
    (``per_scenario`` distinguishes the two). On the device plan path they
    are jax device arrays (f32) instead of host numpy (f64).
    """

    plan: PlanBatch
    policy_idx: np.ndarray   # (k,) columns of the cost matrix this fills
    bid: float
    r_alloc: np.ndarray      # (J, L) | (S, J, L) self-owned instances
    z_t: np.ndarray          # (J, L) | (S, J, L) cloud workload after s-o
    d_eff: np.ndarray        # (J, L) | (S, J, L) cloud parallelism after s-o
    pins: np.ndarray         # bool — tasks holding reservations
    selfowned_work: np.ndarray      # (J,) | (S, J)
    selfowned_reserved: np.ndarray  # (J,) | (S, J)

    @property
    def per_scenario(self) -> bool:
        return self.z_t.ndim == 3


@dataclasses.dataclass
class GridPlan:
    """The full batched evaluation plan for (jobs x policies)."""

    jobs: list[ChainJob]
    policies: list[Policy]
    groups: list[EvalGroup]
    workload: np.ndarray     # (J,) Z_j
    arrival: np.ndarray      # (J,)
    n_jobs: int
    n_policies: int
    L: int
    plan_seconds: float = 0.0   # window-plan tensor construction
    pool_seconds: float = 0.0   # self-owned allocation + residuals
    plan_backend: str = "host"  # "host" (numpy f64) | "device" (jit)
    plan_cached: int = 0        # groups served from the cross-call cache
    jobs_fp: str = ""           # content fingerprint of the job batch
    group_keys: list | None = None  # per-group dedup signatures (cache keys)

    @property
    def device(self) -> bool:
        return self.plan_backend == "device"

    @property
    def bids(self) -> list[float]:
        seen: dict[float, float] = {}
        for g in self.groups:
            seen.setdefault(_bid_key(g.bid), g.bid)
        return sorted(seen.values())

    @property
    def per_scenario(self) -> bool:
        return any(g.per_scenario for g in self.groups)

    def groups_for_bid(self, bid: float) -> list[EvalGroup]:
        key = _bid_key(bid)
        return [g for g in self.groups if _bid_key(g.bid) == key]


def _window_key(policy: Policy, r_total: int, windows: str):
    if windows == "even":
        return ("even",)
    return ("dealloc", round(policy.dealloc_param(r_total), 12))


def distinct_window_params(policies, r_total: int,
                           windows: str = "dealloc") -> dict[tuple, float]:
    """Window-key dedup of a policy grid: {window key -> exact Dealloc param
    of the FIRST policy carrying it} in first-appearance order (the rounded
    key only dedups; the plan is always built from the exact parameter).
    The single source of the dedup rule — the engine, the pipeline
    benchmark, and the bit-compat tests all measure the same grid."""
    key_param: dict[tuple, float] = {}
    for pol in policies:
        wkey = _window_key(pol, r_total, windows)
        if wkey not in key_param:
            key_param[wkey] = (pol.dealloc_param(r_total)
                               if windows != "even" else 0.0)
    return key_param


@dataclasses.dataclass
class _GridStructure:
    """First-appearance-ordered dedup of the (window, beta_0, bid) grid —
    the host-side index arithmetic both plan backends share, so grouping
    is identical by construction."""

    key_param: dict[tuple, float]   # window key -> exact Dealloc param
    a_plan: list[int]               # akey -> window-plan index
    a_beta0: list[float | None]     # akey -> beta_0 of its first policy
    g_akey: list[int]               # group -> akey index
    g_bid: list[float]              # group -> exact bid of its first policy
    g_pols: list[list[int]]         # group -> policy columns it fills
    g_key: list[tuple]              # group -> full (window, b0, bid) key


def _grid_structure(policies, r_total: int, windows: str) -> _GridStructure:
    key_param = distinct_window_params(policies, r_total, windows)
    w_index = {k: i for i, k in enumerate(key_param)}
    akey_index: dict[tuple, int] = {}
    g_index: dict[tuple, int] = {}
    s = _GridStructure(key_param, [], [], [], [], [], [])
    for pi, pol in enumerate(policies):
        wkey = _window_key(pol, r_total, windows)
        b0 = None if pol.beta0 is None else round(pol.beta0, 12)
        akey = wkey + (b0,)
        ai = akey_index.get(akey)
        if ai is None:
            ai = akey_index[akey] = len(s.a_plan)
            s.a_plan.append(w_index[wkey])
            s.a_beta0.append(pol.beta0)
        gkey = akey + (_bid_key(pol.bid),)
        gi = g_index.get(gkey)
        if gi is None:
            gi = g_index[gkey] = len(s.g_bid)
            s.g_akey.append(ai)
            s.g_bid.append(pol.bid)
            s.g_pols.append([pi])
            s.g_key.append(gkey)
        else:
            s.g_pols[gi].append(pi)
    return s


def _cloud_residuals(plan: PlanBatch, r_alloc: np.ndarray):
    """The market-independent tail of ``_simulate_plan``: residual cloud
    workload (dust-killed), effective parallelism, pins, self-owned stats.
    ``r_alloc`` may carry a leading scenario axis; everything broadcasts."""
    sizes = plan.sizes
    z_t = np.maximum(plan.z - r_alloc * sizes, 0.0)
    z_t[z_t <= 1e-9 * (plan.z + 1.0)] = 0.0
    d_eff = np.maximum(plan.delta - r_alloc, 0.0)
    selfowned = np.minimum(r_alloc * sizes, plan.z)
    return z_t, d_eff, r_alloc > 0, selfowned.sum(axis=-1), \
        (r_alloc * sizes).sum(axis=-1)


def build_grid_plan(
    jobs: list[ChainJob],
    policies: list[Policy],
    r_total: int = 0,
    windows: str = "dealloc",
    selfowned: str = "prop12",
    pool: str = "dedicated",
    availability=None,
    slots_per_unit: int = 12,
    n_scenarios: int | None = None,
    plan_backend: str = "host",
    mesh=None,
) -> GridPlan:
    """Deduplicate (jobs x policies) into evaluation groups.

    ``pool="dedicated"`` scores each policy against an uncontended pool (the
    counterfactual evaluator TOLA uses; ``availability`` optionally replaces
    the constant ``r_total`` with a realized residual-occupancy query, or a
    LIST of per-scenario queries — one per market scenario of the batch —
    for scenario-batched pool refinement; pass ``n_scenarios`` so the list
    length is validated HERE, before an (S', J, L) stack of the wrong S
    ships to a backend).
    ``pool="shared"`` replays the chronological shared-pool allocation per
    policy (the realized ``run_jobs`` semantics used by fixed-policy sweeps).
    ``plan_backend="device"`` builds the plan tensors as one fused jit
    program (see module docstring); requires jax and ``pool="dedicated"``.
    ``mesh`` (a ``GridMesh``) does not change the built tensors, but its
    (data, model) partition joins the cross-call plan-cache key: a cached
    group's device buffers are only reused by calls that will shard them
    identically, so warm hits stay bitwise per partition.
    """
    if pool not in ("dedicated", "shared"):
        raise ValueError(f"unknown pool mode {pool!r}")
    if plan_backend not in _PLAN_BACKENDS:
        raise ValueError(f"unknown plan backend {plan_backend!r}; pick from "
                         f"{_PLAN_BACKENDS}")
    if isinstance(availability, (list, tuple)) and n_scenarios is not None \
            and len(availability) != n_scenarios:
        raise ValueError(
            f"per-scenario availability needs one query per scenario "
            f"({len(availability)} queries, {n_scenarios} scenarios)")
    if plan_backend == "device" and pool == "shared":
        raise ValueError(
            "plan_backend='device' supports pool='dedicated' only (the "
            "chronological shared-pool replay is host code)")

    structure = _grid_structure(policies, r_total, windows)
    with span("plan.arrays"):
        arrays = job_arrays(jobs)
    with span("plan.fingerprint"):
        jobs_fp = _cache.fingerprint_job_arrays(arrays)
    # Availability queries are opaque host callables — their results have
    # no fingerprint, so refined plans never enter the cross-call cache.
    use_cache = availability is None and _cache.enabled()
    mesh_part = None if mesh is None else (mesh.data_shards,
                                           mesh.model_shards)
    if plan_backend == "device":
        return _build_grid_plan_device(jobs, policies, structure, arrays,
                                       r_total, windows, selfowned,
                                       availability, jobs_fp=jobs_fp,
                                       use_cache=use_cache,
                                       mesh_part=mesh_part)
    return _build_grid_plan_host(jobs, policies, structure, arrays, r_total,
                                 windows, selfowned, pool, availability,
                                 slots_per_unit, jobs_fp=jobs_fp,
                                 use_cache=use_cache, mesh_part=mesh_part)


def _cache_lookup(s: _GridStructure, base: tuple, use_cache: bool):
    """Consult the cross-call group cache: {group index -> cached record}
    plus the miss list, with hit/miss counters emitted. The miss set
    drives SUBSET builds below — only the window plans and allocations
    the missing groups actually need are recomputed, and building a
    subset of the Dealloc parameters is bit-identical to building all of
    them (``build_plans_batch`` vectorizes per parameter)."""
    with span("plan.lookup") as sp:
        cached: dict[int, EvalGroup] = {}
        if use_cache:
            for gi in range(len(s.g_bid)):
                rec = _cache.PLAN_CACHE.get((base, s.g_key[gi]))
                if rec is not None:
                    cached[gi] = rec
            _cache.plan_cache_events(hits=len(cached),
                                     misses=len(s.g_bid) - len(cached))
        miss = [gi for gi in range(len(s.g_bid)) if gi not in cached]
        sp.set(hits=len(cached), misses=len(miss))
    return cached, miss


def _build_grid_plan_host(jobs, policies, s: _GridStructure, arrays, r_total,
                          windows, selfowned, pool, availability,
                          slots_per_unit, jobs_fp: str = "",
                          use_cache: bool = False,
                          mesh_part=None) -> GridPlan:
    # ``mesh_part`` partitions the cache by (data, model) shard counts so a
    # warm hit never hands one partition another partition's buffers.
    base = (jobs_fp, float(r_total), windows, selfowned, pool,
            int(slots_per_unit), "host", mesh_part)
    cached, miss = _cache_lookup(s, base, use_cache)
    need_ai = sorted({s.g_akey[gi] for gi in miss})
    need_w = sorted({s.a_plan[ai] for ai in need_ai})
    w_pos = {w: i for i, w in enumerate(need_w)}
    params = list(s.key_param.values())

    # Spans are emitted even on an all-hit call: timings["plan"/"pool"]
    # must stay the same floats as the span tracer's totals (test_obs).
    with span("plan", plan_backend="host", windows=windows,
              n_plans=len(need_w), n_cached=len(cached)) as sp:
        if not need_w:
            built: list[PlanBatch] = []
        elif windows == "even":
            built = build_plans_batch(jobs, windows="even", arrays=arrays)
        else:
            built = build_plans_batch(jobs, [params[w] for w in need_w],
                                      windows="dealloc", arrays=arrays)
    plan_seconds = sp.seconds

    with span("pool", plan_backend="host", pool=pool,
              n_groups=len(miss)) as sp:
        alloc: dict[int, np.ndarray] = {
            ai: _group_alloc(built[w_pos[s.a_plan[ai]]], s.a_beta0[ai],
                             r_total, selfowned, pool, availability,
                             slots_per_unit)
            for ai in need_ai}
        groups: list[EvalGroup] = []
        for gi in range(len(s.g_bid)):
            rec = cached.get(gi)
            if rec is not None:
                # The cached record keeps ITS exact bid: two bids rounding
                # to the same 12-decimal key are one group, in-grid and
                # cross-call alike, so the hit is bitwise.
                groups.append(dataclasses.replace(
                    rec, policy_idx=np.asarray(s.g_pols[gi])))
                continue
            ai = s.g_akey[gi]
            plan = built[w_pos[s.a_plan[ai]]]
            r_alloc = alloc[ai]
            z_t, d_eff, pins, so_work, so_res = _cloud_residuals(plan,
                                                                 r_alloc)
            g = EvalGroup(
                plan=plan, policy_idx=np.asarray(s.g_pols[gi]),
                bid=s.g_bid[gi], r_alloc=r_alloc, z_t=z_t, d_eff=d_eff,
                pins=pins, selfowned_work=so_work, selfowned_reserved=so_res)
            groups.append(g)
            if use_cache:
                _cache.PLAN_CACHE.put((base, s.g_key[gi]), g)
    pool_seconds = sp.seconds
    return GridPlan(jobs=jobs, policies=policies, groups=groups,
                    workload=arrays.z.sum(axis=1), arrival=arrays.arrival,
                    n_jobs=len(jobs), n_policies=len(policies),
                    L=arrays.z.shape[1], plan_seconds=plan_seconds,
                    pool_seconds=pool_seconds, plan_backend="host",
                    plan_cached=len(cached), jobs_fp=jobs_fp,
                    group_keys=list(s.g_key))


def _group_alloc(plan: PlanBatch, pol_beta0: float | None, r_total: int,
                 selfowned: str, pool: str, availability,
                 slots_per_unit: int) -> np.ndarray:
    if r_total <= 0:
        return np.zeros_like(plan.z)
    beta0 = np.full(plan.z.shape[0],
                    np.nan if pol_beta0 is None else pol_beta0)
    if pool == "shared":
        # Chronological shared-pool replay on the planned windows; each
        # policy of a sweep owns a fresh pool (sweep semantics of run_jobs).
        # bid is deliberately NaN: the allocation is bid-independent (and is
        # cached per (windows, beta0) across bids) — if _allocate_pool ever
        # starts consulting the bid, this surfaces loudly and the alloc
        # cache key must gain the bid.
        pplan = dataclasses.replace(plan, beta0=beta0,
                                    bid=np.full(plan.z.shape[0], np.nan))
        r_alloc, _ = _allocate_pool(pplan, r_total, selfowned, slots_per_unit)
        return r_alloc
    if availability is None:
        avail = float(r_total)
    elif isinstance(availability, (list, tuple)):
        # Per-scenario residual-occupancy queries -> (S, J, L) availability.
        avail = np.stack([_query(q, plan.starts, plan.ends)
                          for q in availability])
    else:
        avail = _query(availability, plan.starts, plan.ends)
    r_alloc = _selfowned_counts_vec(
        plan.z, plan.delta, plan.sizes, beta0[:, None], avail, selfowned)
    return np.where(plan.mask, r_alloc, 0.0)


# --------------------------------------------------------------------------
# Device plan path: jobs -> plan tensors as ONE fused jit program.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)   # bounded: one entry per mode pair
def _device_plan_fns(selfowned_mode: str, windows: str):
    """Jitted device builders, cached per (self-owned mode, window mode).

    ``full`` is the fused query-free program (windows -> plans -> policy-(12)
    counts -> residuals -> group gather, one XLA computation); ``plans`` /
    ``groups`` are the same pieces split so availability queries (host
    callables) can run between them.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.dealloc import _jax_impls
    from repro.core.scheduler import _selfowned_counts_impl

    waterfill = _jax_impls()["window_sizes_batch"]
    counts_fn = _selfowned_counts_impl(selfowned_mode)

    def plans(e, delta, mask, omega, arrival, xs):
        if windows == "even":
            # xs carries the per-job Even slack share (slack_even / l).
            sizes = jnp.where(mask, e + xs[:, None], 0.0)[None]
        else:
            sizes = waterfill(e, delta, mask, omega, xs)
        cum = jnp.cumsum(sizes, axis=2)
        ends = arrival[None, :, None] + cum
        first = jnp.broadcast_to(arrival[None, :, None],
                                 sizes.shape[:2] + (1,))
        starts = jnp.concatenate([first, ends[:, :, :-1]], axis=2)
        # The raw waterfill sizes ride along: recomputing them as
        # ends - starts would round-trip through the cumsum and inflate the
        # f32 noise ~L-fold, blowing the policy-(12) knife-edge guards
        # (every fully-capped task sits EXACTLY at f(beta_0) = 0).
        return sizes, starts, ends

    def groups(z, delta, mask, sizes, plan_of_akey, b0_of_akey,
               avail, akey_of_group):
        sizes_a = sizes[plan_of_akey]                   # (Ga, J, L)
        b0 = b0_of_akey[:, None, None]
        if avail.ndim == 4:                             # (Ga, S, J, L)
            sizes_a = sizes_a[:, None]
            b0 = b0[:, None]
        # Broadcast up front: a counts rule need not touch every operand
        # (naive = min(avail, delta) ignores the sizes), but the group
        # gather below indexes axis 0 as the akey axis, so r must carry
        # the full combined shape.
        shape = jnp.broadcast_shapes(sizes_a.shape, jnp.shape(avail),
                                     z.shape)
        r = jnp.broadcast_to(
            jnp.where(mask, counts_fn(z, delta, sizes_a, b0, avail), 0.0),
            shape)
        z_t = jnp.maximum(z - r * sizes_a, 0.0)
        z_t = jnp.where(z_t <= _DEVICE_DUST * (z + 1.0), 0.0, z_t)
        d_eff = jnp.maximum(delta - r, 0.0)
        so_work = jnp.minimum(r * sizes_a, z).sum(axis=-1)
        so_res = (r * sizes_a).sum(axis=-1)
        gi = akey_of_group
        return (r[gi], z_t[gi], d_eff[gi], r[gi] > 0,
                so_work[gi], so_res[gi])

    def full(e, delta, mask, omega, arrival, z, xs, plan_of_akey,
             b0_of_akey, avail, akey_of_group):
        sizes, starts, ends = plans(e, delta, mask, omega, arrival, xs)
        return (starts, ends) + groups(z, delta, mask, sizes,
                                       plan_of_akey, b0_of_akey, avail,
                                       akey_of_group)

    return {"plans": jax.jit(plans), "groups": jax.jit(groups),
            "full": jax.jit(full)}


def _build_grid_plan_device(jobs, policies, s: _GridStructure, arrays,
                            r_total, windows, selfowned, availability,
                            jobs_fp: str = "",
                            use_cache: bool = False,
                            mesh_part=None) -> GridPlan:
    import jax
    import jax.numpy as jnp

    # Same validation the host waterfill performs (device code would
    # silently clamp instead of raising).
    if np.any(arrays.omega < -1e-9):
        raise ValueError("infeasible job: window < critical path")
    if windows == "even":
        xs = np.maximum(arrays.slack_even(), 0.0) / arrays.l
    else:
        xs = np.fromiter(s.key_param.values(), dtype=np.float64)
        if np.any((xs <= 0.0) | (xs > 1.0)):
            bad = xs[(xs <= 0.0) | (xs > 1.0)][0]
            raise ValueError(f"Dealloc parameter must be in (0, 1], got {bad}")
    fns = _device_plan_fns(selfowned, windows)

    if availability is None or r_total <= 0:
        return _device_query_free(jobs, policies, s, arrays, r_total,
                                  windows, selfowned, xs, fns, jobs_fp,
                                  use_cache, mesh_part=mesh_part)
    plan_of_akey = np.asarray(s.a_plan, np.int32)
    b0 = np.asarray([np.nan if b is None else b for b in s.a_beta0])
    akey_of_group = np.asarray(s.g_akey, np.int32)
    plans_args = (arrays.e, arrays.delta, arrays.mask, arrays.omega,
                  arrays.arrival, xs)
    record_jit("plan.device.plans", fns["plans"], *plans_args)
    with span("plan", plan_backend="device", windows=windows) as sp:
        sizes, starts, ends = jax.block_until_ready(
            fns["plans"](*plans_args))
    plan_seconds = sp.seconds
    # Availability queries are host callables: stage the planned windows
    # out once, query per distinct (plan, beta_0) cell, ship back.
    with span("pool", plan_backend="device") as sp:
        h_starts, h_ends = np.asarray(starts), np.asarray(ends)
        if isinstance(availability, (list, tuple)):
            avail = np.stack([[_query(q, h_starts[p], h_ends[p])
                               for q in availability]
                              for p in plan_of_akey])
        else:
            avail = np.stack([_query(availability, h_starts[p], h_ends[p])
                              for p in plan_of_akey])
        group_args = (arrays.z, arrays.delta, arrays.mask, sizes,
                      plan_of_akey, b0, jnp.asarray(avail),
                      akey_of_group)
        record_jit("plan.device.groups", fns["groups"], *group_args)
        parts = jax.block_until_ready(fns["groups"](*group_args))
    pool_seconds = sp.seconds

    nan = np.full(len(jobs), np.nan)
    dev_plans = [PlanBatch(arrival=arrays.arrival, starts=starts[w],
                           ends=ends[w], z=arrays.z, delta=arrays.delta,
                           mask=arrays.mask, bid=nan, beta0=nan)
                 for w in range(len(s.key_param))]
    r_g, z_t_g, d_eff_g, pins_g, so_w_g, so_r_g = parts
    # The self-owned stats are consumed host-side only (the EngineResult
    # scatter); ship the two small stacks across once here instead of one
    # device sync per group later. Everything the cost kernels read
    # (ends/starts, z_t, d_eff, pins) stays on device.
    so_w_g, so_r_g = np.asarray(so_w_g), np.asarray(so_r_g)
    groups = [EvalGroup(plan=dev_plans[s.a_plan[s.g_akey[gi]]],
                        policy_idx=np.asarray(s.g_pols[gi]),
                        bid=s.g_bid[gi], r_alloc=r_g[gi], z_t=z_t_g[gi],
                        d_eff=d_eff_g[gi], pins=pins_g[gi],
                        selfowned_work=so_w_g[gi],
                        selfowned_reserved=so_r_g[gi])
              for gi in range(len(s.g_bid))]
    return GridPlan(jobs=jobs, policies=policies, groups=groups,
                    workload=arrays.z.sum(axis=1), arrival=arrays.arrival,
                    n_jobs=len(jobs), n_policies=len(policies),
                    L=arrays.z.shape[1], plan_seconds=plan_seconds,
                    pool_seconds=pool_seconds, plan_backend="device",
                    jobs_fp=jobs_fp, group_keys=list(s.g_key))


def _device_query_free(jobs, policies, s: _GridStructure, arrays, r_total,
                       windows, selfowned, xs, fns, jobs_fp: str,
                       use_cache: bool, mesh_part=None) -> GridPlan:
    """The default (query-free) device plan path, cache-aware.

    Misses run through the SAME fused jit program as before, over the
    SUBSET of window params / akeys / groups they need — on a cold cache
    the subset is the full grid, so the traced shapes (and therefore the
    compiled programs) are identical to the uncached path. On an all-hit
    call no device program runs at all.
    """
    import jax

    base = (jobs_fp, float(r_total), windows, selfowned, "device",
            mesh_part)
    cached, miss = _cache_lookup(s, base, use_cache)
    need_ai = sorted({s.g_akey[gi] for gi in miss})
    ai_pos = {ai: i for i, ai in enumerate(need_ai)}
    need_w = sorted({s.a_plan[ai] for ai in need_ai})
    w_pos = {w: i for i, w in enumerate(need_w)}
    if windows == "even":
        xs_sub = xs                         # per-job slack, single plan
    else:
        xs_sub = xs[np.asarray(need_w, np.intp)]
    plan_of_akey = np.asarray([w_pos[s.a_plan[ai]] for ai in need_ai],
                              np.int32)
    b0 = np.asarray([np.nan if s.a_beta0[ai] is None else s.a_beta0[ai]
                     for ai in need_ai])
    akey_of_group = np.asarray([ai_pos[s.g_akey[gi]] for gi in miss],
                               np.int32)

    if miss:
        full_args = (arrays.e, arrays.delta, arrays.mask, arrays.omega,
                     arrays.arrival, arrays.z, xs_sub, plan_of_akey, b0,
                     float(max(r_total, 0)), akey_of_group)
        record_jit("plan.device.full", fns["full"], *full_args)
    with span("plan", plan_backend="device", windows=windows,
              n_cached=len(cached)) as sp:
        if miss:
            # The fused program: no host staging between windows and
            # residuals.
            out = jax.block_until_ready(fns["full"](*full_args))
    plan_seconds = sp.seconds

    new_groups: dict[int, EvalGroup] = {}
    if miss:
        (starts, ends), parts = out[:2], out[2:]
        nan = np.full(len(jobs), np.nan)
        dev_plans = [PlanBatch(arrival=arrays.arrival, starts=starts[i],
                               ends=ends[i], z=arrays.z, delta=arrays.delta,
                               mask=arrays.mask, bid=nan, beta0=nan)
                     for i in range(starts.shape[0])]
        r_g, z_t_g, d_eff_g, pins_g, so_w_g, so_r_g = parts
        # The self-owned stats are consumed host-side only (the
        # EngineResult scatter); ship the two small stacks across once
        # here instead of one device sync per group later. Everything the
        # cost kernels read (ends/starts, z_t, d_eff, pins) stays on
        # device.
        so_w_g, so_r_g = np.asarray(so_w_g), np.asarray(so_r_g)
        for k, gi in enumerate(miss):
            g = EvalGroup(plan=dev_plans[w_pos[s.a_plan[s.g_akey[gi]]]],
                          policy_idx=np.asarray(s.g_pols[gi]),
                          bid=s.g_bid[gi], r_alloc=r_g[k], z_t=z_t_g[k],
                          d_eff=d_eff_g[k], pins=pins_g[k],
                          selfowned_work=so_w_g[k],
                          selfowned_reserved=so_r_g[k])
            new_groups[gi] = g
            if use_cache:
                _cache.PLAN_CACHE.put((base, s.g_key[gi]), g)
    groups = [
        dataclasses.replace(cached[gi], policy_idx=np.asarray(s.g_pols[gi]))
        if gi in cached else new_groups[gi]
        for gi in range(len(s.g_bid))]
    return GridPlan(jobs=jobs, policies=policies, groups=groups,
                    workload=arrays.z.sum(axis=1), arrival=arrays.arrival,
                    n_jobs=len(jobs), n_policies=len(policies),
                    L=arrays.z.shape[1], plan_seconds=plan_seconds,
                    pool_seconds=0.0, plan_backend="device",
                    plan_cached=len(cached), jobs_fp=jobs_fp,
                    group_keys=list(s.g_key))
