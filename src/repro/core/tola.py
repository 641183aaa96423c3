"""TOLA / OptiLearning — the online-learning layer (paper Alg. 4, App. B.2).

Exponentiated-weights over a finite policy grid. When job j arrives at
``a_j`` a policy is sampled from the current weight distribution and drives
the job's actual allocation. Once a job's window has fully elapsed
(``t = a_j + d`` with d the max relative deadline, so all spot prices inside
every window are known), its cost under EVERY policy of the grid is computed
counterfactually and the weights are re-scaled with
``w <- w * exp(-eta_t * c_j(pi))``.

Implementation notes (faithful, but vectorized):

* The counterfactual cost matrix ``C[j, pi]`` does not depend on the weight
  evolution, so it is precomputed with one batched engine pass
  (``repro.engine.evaluate_grid``); the sequential sample/update replay is
  delegated to the online-learning subsystem ``repro.learn`` — the numpy
  backend there is the exact float64 oracle, bit-compatible with the
  original in-module event loop (same logw arithmetic, same uniform-stream
  consumption as ``rng.choice``), and ``learner`` swaps in the bandit
  learners (EXP3/UCB1/epsilon-greedy/FTL) of ``repro.learn.learners``.
* Per-job losses are normalized by the job workload Z_j (the paper's own
  performance metric is cost per unit workload); unnormalized costs reach
  O(10^4) and exp(-eta*c) would underflow the weight update. This keeps
  losses in [0, p_od], as the regret bound of Prop. B.1 assumes.
* The realized pass replays the sampled policies chronologically against the
  shared self-owned pool (same plan machinery as ``run_jobs``).

Round 0's engine call runs inside the span ``tola.score`` and each pool
refinement's inside ``tola.rescore`` (attribute ``round``). Inside
``METRICS.collecting()`` every round sets the gauge
``tola.selfowned_share{round}``: the share of the stream's work the realized
run did on self-owned instances, averaged over the markets.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.market import SpotMarket
from repro.core.scheduler import (
    Policy,
    StreamCosts,
    _allocate_pool,
    _simulate_plan,
    build_plans,
)
from repro.core.types import ChainJob
from repro.obs import METRICS, span

__all__ = ["TolaResult", "run_tola_scenarios"]


@dataclasses.dataclass
class TolaResult:
    chosen: np.ndarray          # (n_jobs,) sampled policy index per job
    weights: np.ndarray         # (n_policies,) final distribution
    realized: StreamCosts       # realized costs under the sampled policies
    cost_matrix: np.ndarray     # (n_jobs, n_policies) counterfactual unit costs
    fixed_unit_costs: np.ndarray  # (n_policies,) stream alpha per fixed policy
    learn: "object | None" = None  # repro.learn.LearnResult of the last iter

    def average_unit_cost(self) -> float:
        return self.realized.average_unit_cost()

    @property
    def best_fixed_unit_cost(self) -> float:
        return float(self.fixed_unit_costs.min())

    @property
    def regret_per_job(self) -> float:
        """Realized average excess unit cost vs the best fixed policy."""
        return self.average_unit_cost() - self.best_fixed_unit_cost


def _engine_span(round_: int):
    """The span around one round's engine call: ``tola.score`` for round 0,
    ``tola.rescore`` for each pool refinement."""
    if round_ == 0:
        return span("tola.score")
    return span("tola.rescore", round=round_)


def _record_selfowned_share(realized: list[StreamCosts], round_: int) -> None:
    """Gauge ``tola.selfowned_share{round}``: realized self-owned share of
    the stream's work, averaged over the markets."""
    if METRICS.enabled:
        share = np.mean([r.selfowned_work.sum() / r.workload.sum()
                         for r in realized])
        METRICS.gauge("tola.selfowned_share").set(share, round=round_)


def _residual_availability(pool, r_total: int, slot: float):
    """Query fn: realized residual pool capacity over planned windows."""
    from repro.core.pool import RangeMax

    rmax = RangeMax(pool.used)

    def query(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
        lo = np.floor(starts / slot + 1e-9).astype(np.int64)
        hi = np.ceil(ends / slot - 1e-9).astype(np.int64)
        return np.maximum(r_total - rmax.query(lo, np.maximum(hi, lo + 1)), 0.0)

    return query


def _stream_meta(jobs: list[ChainJob]):
    """(arrivals, d, Z) of an arrival-ordered stream, validated."""
    arrivals = np.array([j.arrival for j in jobs])
    if np.any(np.diff(arrivals) < -1e-9):
        raise ValueError("jobs must be arrival-ordered")
    d = max(j.deadline - j.arrival for j in jobs)
    Z = np.array([j.total_work for j in jobs])
    return arrivals, d, Z


def _tola_round(jobs, policies, C, arrivals, d, Z, spec, rng, market,
                r_total, windows, selfowned, early_start, round_=0,
                scenario=0):
    """One Alg.-4 round for one scenario: replay the learner over C, run the
    sampled policies against the shared pool, return the realized residual-
    availability query for the next refinement."""
    from repro.learn import replay as learn_replay

    with span("tola.round", round=round_, scenario=scenario):
        lr = learn_replay(C, arrivals, d, workload=Z, learners=[spec],
                          rng=rng, backend="numpy")
        chosen = lr.chosen[0, 0]
        with span("tola.plans"):
            plan = build_plans(jobs, [policies[c] for c in chosen], r_total,
                               windows)
        with span("tola.pool"):
            r_alloc, pool = _allocate_pool(plan, r_total, selfowned,
                                           market.slots_per_unit)
        with span("tola.realize"):
            realized = _simulate_plan(plan, r_alloc, market, early_start)
        with span("tola.availability"):
            availability = None if pool is None else \
                _residual_availability(pool, r_total, market.slot)
    return lr, chosen, realized, availability


def run_tola_scenarios(
    jobs: list[ChainJob],
    policies: list[Policy],
    markets: list[SpotMarket],
    r_total: int = 0,
    seed: int = 0,
    windows: str = "dealloc",
    selfowned: str = "prop12",
    early_start: bool = True,
    pool_iters: int = 1,
    backend: str = "auto",
    learner="hedge",
    mesh=None,
) -> list[TolaResult]:
    """Full Algorithm 4 over an arrival-ordered job list, in each of S
    market scenarios, cost matrices batched. One market is ``[market]``:
    ``run_tola_scenarios(jobs, policies, [market], ...)[0]``.

    ``pool_iters``: number of pool-aware refinements of the counterfactual
    cost matrix. Round 0 scores policies against a dedicated pool (the
    [10]/[12] simplification); each refinement re-scores them against the
    residual availability realized by the previous round's run — without
    this, the learner never sees self-owned scarcity and over-rewards
    pool-hogging (small beta_0) policies.

    Exactly ONE ``evaluate_grid`` call per round, covering every scenario:
    round 0 is the engine's ordinary scenario axis; each pool refinement
    re-scores the grid against the S realized residual-availability
    queries in a single per-scenario-availability pass (the engine stacks
    the refined plan tensors along the scenario axis). ``backend`` selects
    the engine backend; the sequential sample/update replay always runs
    the float64 numpy oracle of ``repro.learn`` per scenario, with seed
    ``seed + s`` (Hedge there is bit-compatible with the original
    in-module loop, Table 6 output included). ``learner`` is a kind name
    or ``LearnerSpec`` from ``repro.learn.learners``.

    ``mesh`` shards the scenario axis across a device mesh (DESIGN.md §9)
    in EVERY round: round 0 shards the ordinary scenario axis, and the
    refinement rounds shard the per-scenario-availability pass — the
    (S, R, L) refined plan stacks ride the ``"data"`` axis next to the
    views, group rows the ``"model"`` axis, with zero collectives in the
    eval hot loop.
    """
    from repro.engine import evaluate_grid
    from repro.learn import as_spec

    if not jobs or not policies:
        raise ValueError("need jobs and policies")
    S = len(markets)
    arrivals, d, Z = _stream_meta(jobs)
    spec = as_spec(learner)
    rngs = [np.random.default_rng(seed + s) for s in range(S)]

    avails: list | None = None
    iters = 1 + (pool_iters if r_total > 0 else 0)
    with span("tola"):
        for it in range(iters):
            with _engine_span(it):
                res = evaluate_grid(
                    jobs, policies, markets, r_total, windows=windows,
                    selfowned=selfowned, early_start=early_start,
                    pool="dedicated", availability=avails, backend=backend,
                    mesh=mesh)
            C = res.unit_cost
            rounds = [
                _tola_round(jobs, policies, C[s], arrivals, d, Z, spec,
                            rngs[s], markets[s], r_total, windows, selfowned,
                            early_start, round_=it, scenario=s)
                for s in range(S)
            ]
            _record_selfowned_share([r[2] for r in rounds], it)
            avails = [r[3] for r in rounds]
            if any(a is None for a in avails):
                avails = None  # r_total == 0: nothing to refine against

    return [
        TolaResult(chosen=chosen, weights=lr.weights[0, 0],
                   realized=realized, cost_matrix=C[s],
                   fixed_unit_costs=(C[s] * Z[:, None]).sum(axis=0) / Z.sum(),
                   learn=lr)
        for s, (lr, chosen, realized, _) in enumerate(rounds)
    ]
