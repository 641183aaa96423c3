"""Shared harness for the paper-table benchmarks (Experiments 1-4).

Job streams and the market follow Section 6.1 exactly; see
``repro.core.workload`` / ``repro.core.market`` for the distributional
details and DESIGN.md Section 4 for the two documented interpretation
choices (price law, early starts).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import generate_chain_jobs, sweep_policies
from repro.core.scheduler import Policy
from repro.engine import (
    ScenarioSpec,
    as_source,
    make_scenarios,
    setup_persistent_cache,
)

__all__ = ["Setup", "make_setup", "sweep_min", "greedy_min",
           "argparser", "print_table"]

# Reuse XLA executables across benchmark PROCESSES (DESIGN.md §11) so
# repeated paper-table runs skip recompilation. bench_pipeline times cold
# compiles and therefore does not import this module.
setup_persistent_cache()


class Setup:
    def __init__(self, jobs, scenarios, job_type: int, seed: int,
                 backend: str = "auto", scenario_chunk: int | None = None,
                 mesh=None):
        self.jobs = jobs
        self.scenarios = scenarios      # ScenarioSource | ScenarioSpec
        self.job_type = job_type
        self.seed = seed
        self.backend = backend
        self.scenario_chunk = scenario_chunk
        self.mesh = mesh                # GridMesh | int | None
        self._source = as_source(scenarios)

    @property
    def markets(self):
        """Materialized scenario markets (host-only consumers: the greedy
        baseline, the realized shared-pool TOLA replay)."""
        return self._source.markets

    @property
    def market(self):
        """Scenario 0 — the single market of the paper's tables."""
        return self.markets[0]

    @property
    def total_workload(self) -> float:
        return float(sum(j.total_work for j in self.jobs))


def make_setup(n_jobs: int, job_type: int, seed: int = 0,
               scenarios: int = 1, scenario_kind: str = "fresh",
               backend: str = "auto",
               scenario_chunk: int | None = None, mesh=None) -> Setup:
    """Job stream + S market scenarios (S=1 reproduces the paper setup).

    Without ``scenario_chunk`` the scenarios are the legacy materialized
    ``make_scenarios`` list (bit-compatible with every earlier PR's
    tables). With it, they are a declarative ``ScenarioSpec`` streamed
    through the engine ``scenario_chunk`` scenarios per pass — synthesized
    on device for the jax/pallas backends, S bounded by wall clock rather
    than host memory (``adaptive`` requires this path: it needs the
    stream's chunk-boundary feedback). ``mesh`` (an int shard count from
    ``--mesh``, at most the visible devices) shards the scenario axis
    across a device mesh (DESIGN.md §9; jax backend only).
    """
    jobs = generate_chain_jobs(n_jobs, job_type, seed=seed)
    horizon = max(j.deadline for j in jobs) + 1.0
    if scenario_chunk is not None or scenario_kind == "adaptive":
        if scenario_chunk is None:
            raise ValueError(
                "--scenario-kind adaptive needs --scenario-chunk (the "
                "adversary reacts at chunk boundaries)")
        scn = ScenarioSpec(scenario_kind, horizon, max(scenarios, 1),
                           seed=seed + 1000)
    else:
        scn = make_scenarios(horizon, max(scenarios, 1), seed=seed + 1000,
                             kind=scenario_kind)
    return Setup(jobs, scn, job_type, seed, backend,
                 scenario_chunk=scenario_chunk, mesh=mesh)


def sweep_min(setup: Setup, policies: list[Policy], **kwargs):
    """min over a policy grid of the realized average unit cost.

    One batched engine pass over policies x bids x scenarios (the alpha of
    each policy is its scenario mean); see ``repro.core.sweep_policies``.
    For a materialized list setup the scenario source is reused across
    sweeps, so the stacked per-bid view tensors are built once per bid,
    not once per sweep. (Chunked spec setups trade that cache away on
    purpose: streaming re-synthesizes each chunk so peak memory stays
    chunk-sized.)
    """
    kwargs.setdefault("backend", setup.backend)
    kwargs.setdefault("scenario_chunk", setup.scenario_chunk)
    kwargs.setdefault("mesh", setup.mesh)
    pol, alpha, costs, _ = sweep_policies(setup.jobs, policies,
                                          setup._source, **kwargs)
    return pol, alpha, costs


def greedy_min(setup: Setup, bids) -> float:
    """min over bids of the (scenario-mean) Greedy benchmark alpha."""
    from repro.core import run_greedy

    return min(
        float(np.mean([run_greedy(setup.jobs, b, m).average_unit_cost()
                       for m in setup.markets]))
        for b in bids)


def argparser(desc: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--jobs", type=int, default=1500,
                   help="jobs per stream (paper: ~10000)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--types", type=int, nargs="+", default=[1, 2, 3, 4])
    p.add_argument("--r", type=int, nargs="+", default=[300, 600, 900, 1200])
    p.add_argument("--scenarios", type=int, default=1,
                   help="market scenarios evaluated in one engine pass "
                        "(1 = the paper's single market)")
    p.add_argument("--scenario-kind",
                   choices=["fresh", "regime", "adversarial", "adaptive"],
                   default="fresh",
                   help="market family (adversarial = lure/spike square "
                        "waves driving worst-case TOLA regret; adaptive = "
                        "spikes placed by watching the learner, needs "
                        "--scenario-chunk)")
    p.add_argument("--scenario-chunk", type=int, default=None,
                   help="stream scenarios through the engine K per pass "
                        "from a declarative ScenarioSpec (device-side "
                        "synthesis on jax/pallas; peak memory bounded by "
                        "the chunk, so --scenarios can exceed host memory)")
    p.add_argument("--backend", default="auto",
                   choices=["auto", "numpy", "jax", "pallas"],
                   help="evaluation-engine backend")
    p.add_argument("--mesh", type=int, default=None,
                   help="shard the scenario axis over an N-way device mesh "
                        "(jax backend; N may not exceed the visible devices "
                        "— force N CPU devices with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    return p


def print_table(title: str, header: list[str], rows: list[list[str]]):
    print(f"\n== {title} ==")
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))


class Timer:
    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        print(f"[{self.label}: {time.time() - self.t0:.1f}s]")
