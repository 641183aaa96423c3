"""Backend-dispatching batched evaluation engine (DESIGN.md §6).

The system's hot loop — scoring every job under every policy of the TOLA
grid, across market scenarios — as one batched computation:

    from repro.engine import ScenarioSpec, evaluate_grid
    res = evaluate_grid(jobs, policies, markets, backend="auto")
    C = res.unit_cost[s]          # (n_jobs, n_policies) cost matrix

    # declarative scenario family, synthesized on device, streamed in
    # chunks of 256 — peak memory independent of S under reduce="mean"
    spec = ScenarioSpec("adversarial", horizon, n_scenarios=4096)
    res = evaluate_grid(jobs, policies, spec, scenario_chunk=256,
                        reduce="mean", backend="jax")

Layers: plan (``plan.py`` — deduplicated PlanBatch groups), backends
(``backend_{numpy,jax,pallas}.py``), scenarios (``scenarios.py`` —
declarative ``ScenarioSpec`` families + chunked ``ScenarioStream``s,
DESIGN.md §8).
"""

from repro.engine.api import (
    GridChunk,
    available_backends,
    evaluate_grid,
    evaluate_grid_chunks,
    resolve_backend,
    resolve_plan_backend,
)
from repro.engine.cache import (
    clear_caches,
    evaluate_grid_delta,
    jobs_fingerprint,
    scenario_fingerprint,
    setup_persistent_cache,
)
from repro.engine.cache import configure as configure_caches
from repro.engine.mesh import GridMesh, as_scenario_mesh
from repro.engine.plan import EvalGroup, GridPlan, build_grid_plan
from repro.engine.result import EngineResult
from repro.engine.scenarios import (
    ScenarioBatch,
    ScenarioSpec,
    ScenarioStream,
    adversarial_scenarios,
    as_source,
    check_scenarios,
    make_scenarios,
    replay_scenarios,
    stack_views,
)

__all__ = [
    "evaluate_grid", "evaluate_grid_chunks", "GridChunk",
    "available_backends", "resolve_backend", "resolve_plan_backend",
    "evaluate_grid_delta", "clear_caches", "configure_caches",
    "jobs_fingerprint", "scenario_fingerprint", "setup_persistent_cache",
    "EngineResult", "EvalGroup", "GridPlan", "build_grid_plan",
    "GridMesh", "as_scenario_mesh",
    "ScenarioSpec", "ScenarioStream", "ScenarioBatch", "as_source",
    "make_scenarios", "adversarial_scenarios", "replay_scenarios",
    "check_scenarios", "stack_views",
]
