"""Frozen copy of the paper's §6.1 job-stream generator (arXiv 2106.01847).

The benchmark's traffic must not move when the program's own generator
changes, so this file holds its own copy, drawing from numpy's Generator in
exactly the same order as the program's ``generate_dag_jobs`` did when the
benchmark was written (a test checks the two agree on a short stream).

* Job arrivals: Poisson process, rate 4 per unit time.
* Tasks per job: l drawn uniformly from {7, 49}.
* DAG edges: each pair (i1 < i2) independently with probability 0.5; tasks
  without successors / predecessors get one random connection. Generation
  order is the topological order.
* Parallelism bound delta_i uniform over {8, 64}.
* Minimum execution time e_i: generalized Pareto (shape 7/8, scale 7/32,
  location 1/4) truncated to [2, 10] by exact inverse CDF; z_i = e_i delta_i.
* Relative deadline x * e_c (critical path), x uniform on [1, x0].

It returns plain arrays: the program and the reference each build their own
objects from them.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["DagJob", "generate"]


@dataclasses.dataclass(frozen=True)
class DagJob:
    arrival: float
    deadline: float
    z: np.ndarray          # (l,) task workloads
    delta: np.ndarray      # (l,) parallelism bounds
    preds: tuple           # preds[i]: tuple of predecessor indices < i


def _gpd_cdf(x, xi, sigma, mu):
    return 1.0 - np.power(1.0 + xi * (x - mu) / sigma, -1.0 / xi)


def _gpd_icdf(u, xi, sigma, mu):
    return mu + sigma / xi * (np.power(1.0 - u, -xi) - 1.0)


def _bounded_pareto(rng, n, p):
    shape, scale, loc = p["pareto_shape"], p["pareto_scale"], p["pareto_loc"]
    lo = _gpd_cdf(np.array(p["e_min"]), shape, scale, loc)
    hi = _gpd_cdf(np.array(p["e_max"]), shape, scale, loc)
    u = lo + rng.random(n) * (hi - lo)
    return _gpd_icdf(u, shape, scale, loc)


def _dag_edges(rng, l):
    adj = np.triu(rng.random((l, l)) < 0.5, k=1)
    for i in range(l - 1):
        if not adj[i, i + 1:].any():
            adj[i, rng.integers(i + 1, l)] = True
    for i in range(1, l):
        if not adj[:i, i].any():
            adj[rng.integers(0, i), i] = True
    return tuple(tuple(int(p) for p in np.nonzero(adj[:, i])[0])
                 for i in range(l))


def critical_path(e: np.ndarray, preds) -> float:
    """Longest path through the DAG at full parallelism."""
    q = np.zeros(len(e))
    for i, ps in enumerate(preds):
        if ps:
            q[i] = max(q[p] + e[p] for p in ps)
    return float(np.max(q + e))


def generate(cfg: dict, n_jobs: int, seed: int) -> list[DagJob]:
    """``n_jobs`` DAG jobs of the configuration's job type, from ``seed``."""
    rng = np.random.default_rng(seed)
    x0 = cfg["x0"]
    arrivals = np.cumsum(rng.exponential(1.0 / cfg["arrival_rate"], n_jobs))
    jobs = []
    for j in range(n_jobs):
        l = int(rng.choice(cfg["task_counts"]))
        e = _bounded_pareto(rng, l, cfg)
        delta = rng.choice(np.asarray(cfg["parallelism"], np.float64), l)
        z = e * delta
        preds = _dag_edges(rng, l)
        x = rng.uniform(1.0, x0)
        arrival = float(arrivals[j])
        # e from z / delta, as the stream is consumed downstream.
        cp = critical_path(z / delta, preds)
        jobs.append(DagJob(arrival=arrival, deadline=arrival + x * cp,
                           z=z, delta=delta, preds=preds))
    return jobs
