"""``evaluate_grid`` — the single entry point of the evaluation engine.

Batches the TOLA counterfactual cost matrix (and every fixed-policy sweep)
across policies x bids x market scenarios and dispatches to a backend:

* ``numpy``  — float64 closed-form simulators from ``core/`` (exact oracle);
* ``jax``    — vectorized jnp (``kernels/ref.py``), scenario axis vmapped;
* ``pallas`` — the ``policy_cost_chain`` TPU kernel, ONE launch covering
  the whole (bid x scenario x policy x job) sweep;
* ``auto``   — pallas on TPU/GPU, numpy otherwise.

All backends consume the same deduplicated ``GridPlan`` (see ``plan.py``)
and fill the same (S, J, P) result tensors, so parity is testable cell by
cell (tests/test_engine.py).

The PLAN layer is backend-parametric too (``plan_backend``): ``"host"`` is
the float64 numpy oracle, ``"device"`` builds the plan tensors as one
fused jit program whose outputs the jax/pallas cost kernels consume
without a host staging copy. ``"auto"`` pairs the device plan with the
jax/pallas eval backends and the host plan with numpy.

The SCENARIO axis is a chunked stream (``scenarios.py``): ``scenarios``
may be a materialized market (list) or a declarative ``ScenarioSpec`` /
``ScenarioStream``, and ``scenario_chunk=K`` evaluates S >> host memory by
synthesizing+consuming K scenarios per pass against ONE grid plan — the
plan layer's dedup structure and the backends' compiled programs are
reused across chunks, and no per-scenario Python object exists on the
jax/pallas hot path (the spec synthesizes price paths on device).
``evaluate_grid_chunks`` exposes the same stream one chunk at a time
(the online-learning replay consumes it without ever materializing the
full (S, J, P) tensor, and adaptive-adversary feedback happens between
chunks).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Iterator, Sequence

import numpy as np

from repro.obs import maybe_snapshot, span

from repro.core.market import SpotMarket
from repro.core.scheduler import Policy
from repro.core.types import ChainJob
from repro.engine.mesh import as_scenario_mesh
from repro.engine.plan import OUT_KEYS as _OUT_KEYS, build_grid_plan
from repro.engine.result import EngineResult
from repro.engine.scenarios import as_source

__all__ = ["evaluate_grid", "evaluate_grid_chunks", "GridChunk",
           "available_backends", "resolve_backend", "resolve_plan_backend"]

_BACKENDS = ("numpy", "jax", "pallas")
_PLAN_BACKENDS = ("host", "device")
_REDUCES = ("stack", "mean")


def available_backends() -> list[str]:
    """Backends usable in this process.

    ``"jax"`` needs an importable jax; ``"pallas"`` additionally needs
    ``jax.experimental.pallas`` — probed for real (some jax builds ship
    without it), so ``--backend pallas`` fails at selection time with a
    clear message instead of mid-run.
    """
    out = ["numpy"]
    try:
        import jax  # noqa: F401
    except Exception:
        return out
    out.append("jax")
    try:
        import jax.experimental.pallas  # noqa: F401
        out.append("pallas")
    except Exception:
        pass
    return out


def resolve_backend(backend: str) -> str:
    """Resolve "auto" (env override REPRO_ENGINE_BACKEND honored first)."""
    if backend == "auto":
        env = os.environ.get("REPRO_ENGINE_BACKEND", "auto")
        if env not in _BACKENDS + ("auto",):
            # Validated separately from the caller's argument: the generic
            # "unknown backend" error below would blame the caller's
            # "auto" for a bad environment value.
            raise ValueError(
                f"invalid REPRO_ENGINE_BACKEND={env!r} environment "
                f"override; pick from {_BACKENDS + ('auto',)}")
        backend = env
    if backend == "auto":
        # No fallback: a jax that cannot import or find its platform is an
        # error here, not a silent switch to the host oracle.
        import jax

        return "pallas" if jax.default_backend() != "cpu" else "numpy"
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from "
                         f"{_BACKENDS + ('auto',)}")
    avail = available_backends()
    if backend not in avail:
        why = ("jax imports but jax.experimental.pallas does not"
               if backend == "pallas" and "jax" in avail
               else "jax is not importable in this environment")
        raise ValueError(f"backend {backend!r} is unavailable ({why}); "
                         f"available backends: {avail}")
    return backend


def resolve_plan_backend(plan_backend: str, backend: str,
                         pool: str = "dedicated") -> str:
    """Resolve the plan-layer backend.

    ``"auto"`` follows the (already resolved) eval backend: device plan
    tensors for jax/pallas, host float64 for numpy. The shared-pool replay
    and environments without jax stay on host. Explicit ``"device"`` with
    an incompatible combination raises instead of silently degrading.
    """
    if plan_backend == "auto":
        if backend in ("jax", "pallas") and pool != "shared" \
                and "jax" in available_backends():
            return "device"
        return "host"
    if plan_backend not in _PLAN_BACKENDS:
        raise ValueError(f"unknown plan backend {plan_backend!r}; pick from "
                         f"{_PLAN_BACKENDS + ('auto',)}")
    if plan_backend == "device":
        if backend == "numpy":
            raise ValueError(
                "plan_backend='device' feeds device tensors to the "
                "jax/pallas eval backends; the numpy oracle is host-only "
                "(use plan_backend='host')")
        if pool == "shared":
            raise ValueError(
                "plan_backend='device' supports pool='dedicated' only (the "
                "chronological shared-pool replay is host code)")
        if "jax" not in available_backends():
            raise ValueError("plan_backend='device' requires importable jax")
    return plan_backend


def _check_scenario_chunk(scenario_chunk) -> None:
    """API-boundary validation of ``scenario_chunk`` (same care the
    ``REPRO_ENGINE_BACKEND`` override got: fail HERE, naming the argument,
    not deep in a backend with an opaque shape error)."""
    if scenario_chunk is None:
        return
    if isinstance(scenario_chunk, bool) \
            or not isinstance(scenario_chunk, (int, np.integer)):
        raise ValueError(
            f"scenario_chunk must be an int >= 1 or None "
            f"(got {scenario_chunk!r})")
    if scenario_chunk < 1:
        raise ValueError(
            f"scenario_chunk must be >= 1 (got {scenario_chunk}); pass "
            f"None to evaluate all scenarios in one pass")


def _prepare_stream(jobs, policies, scenarios, r_total, windows, selfowned,
                    pool, availability, backend, plan_backend,
                    scenario_chunk, mesh=None, overlap=None):
    """Shared validation + plan build of the chunked evaluation paths.

    Returns ``(source, gplan, backend, chunk, single, mesh, overlap)`` —
    the grid plan is built ONCE and reused across every scenario chunk (it
    is scenario-independent apart from the per-scenario availability case,
    which requires a single full-batch chunk)."""
    if not jobs:
        raise ValueError("need at least one job")
    policies = list(policies)
    if not policies:
        raise ValueError("need at least one policy")
    single = isinstance(scenarios, SpotMarket)
    source = as_source(scenarios)
    S = source.n_scenarios
    _check_scenario_chunk(scenario_chunk)
    chunk = S if scenario_chunk is None else min(int(scenario_chunk), S)
    if chunk < S and isinstance(availability, (list, tuple)):
        raise ValueError(
            "scenario_chunk cannot split a batch with per-scenario "
            "availability queries (the plan's self-owned tensors are "
            "indexed by the full scenario axis); evaluate in one chunk")

    mesh = as_scenario_mesh(mesh)
    if mesh is not None:
        # The sharded scenario axis is a jax-backend feature: "auto"
        # resolves straight to jax; explicit numpy/pallas cannot consume a
        # mesh and fail here, at the argument that names the conflict.
        backend = "jax" if backend == "auto" else backend
        backend = resolve_backend(backend)
        if backend != "jax":
            raise ValueError(
                f"mesh= shards the scenario axis of the jax backend; "
                f"backend {backend!r} cannot consume a GridMesh "
                f"(drop mesh= or pass backend='jax'/'auto')")
        # Per-scenario availability (refined plans) IS shardable: the
        # (S, R, L) self-owned stacks shard over "data" alongside the
        # views, group rows over "model" — see backend_jax's ps path.
    else:
        backend = resolve_backend(backend)

    if overlap is None:
        overlap = backend != "numpy" and not source.reactive
    elif overlap and source.reactive:
        raise ValueError(
            "overlap=True cannot double-buffer a reactive (adaptive) "
            "scenario stream: chunk k+1's spikes are planned from feedback "
            "about chunk k, so its synthesis cannot be dispatched early")
    overlap = bool(overlap)

    plan_backend = resolve_plan_backend(plan_backend, backend, pool)
    gplan = build_grid_plan(
        jobs, policies, r_total, windows=windows, selfowned=selfowned,
        pool=pool, availability=availability,
        slots_per_unit=source.slots_per_unit,
        n_scenarios=S, plan_backend=plan_backend, mesh=mesh)
    return source, gplan, backend, chunk, single, mesh, overlap


def _dispatch(backend, gplan, batch, early_start, out, mesh=None) -> None:
    if backend == "numpy":
        from repro.engine import backend_numpy
        backend_numpy.run(gplan, batch, early_start, out)
    elif backend == "jax":
        from repro.engine import backend_jax
        backend_jax.run(gplan, batch, early_start, out, mesh=mesh)
    else:
        from repro.engine import backend_pallas
        backend_pallas.run(gplan, batch, early_start, out)


def _prefetched(stream):
    """Double-buffer a chunk stream: DISPATCH chunk k+1's (async, device)
    synthesis before yielding chunk k, so it computes while the consumer
    evaluates k. Lookahead depth 1 — at most two chunks of synthesis
    output are live at once, keeping the chunk-sized-memory contract."""
    prev = None
    for item in stream:
        item[2].dispatch()
        if prev is not None:
            yield prev
        prev = item
    if prev is not None:
        yield prev


def _chunk_loop(source, gplan, backend, chunk, mesh, overlap, early_start,
                out_for):
    """The one scenario-chunk loop of every entry point.

    Synthesizes each chunk of ``source`` and evaluates it into
    ``out_for(s0, s1)``, the per-key dict of (s1 - s0, J, P) arrays the
    backend writes into: the caller decides whether that is a slice of
    its full tensor, a reused buffer or a fresh chunk. Yields
    ``(s0, s1, out, synth_seconds, eval_seconds)`` after each chunk's
    span closes, so the consumer's work between chunks (folds, adaptive
    feedback) runs before the next chunk is built."""
    J, P = gplan.n_jobs, gplan.n_policies
    rows = len(gplan.groups) * J
    stream = source.chunks(chunk, device=(backend != "numpy"), mesh=mesh)
    if overlap:
        stream = _prefetched(stream)
    for ci, (s0, s1, batch) in enumerate(stream):
        with span("chunk", index=ci, s0=s0, s1=s1, backend=backend):
            with span("synth", s0=s0, s1=s1, overlap=overlap) as sp_s:
                batch.prepare()
            out = out_for(s0, s1)
            with span("eval", s0=s0, s1=s1, backend=backend, rows=rows,
                      cells=(s1 - s0) * J * P) as sp_e:
                _dispatch(backend, gplan, batch, early_start, out, mesh)
        yield s0, s1, out, sp_s.seconds, sp_e.seconds


@dataclasses.dataclass
class GridChunk:
    """One scenario chunk of a streamed grid evaluation.

    ``unit_cost[k]`` is the (J, P) cost matrix of GLOBAL scenario
    ``s0 + k``; ``out`` carries the per-cell cost decomposition of the
    chunk. The arrays are chunk-sized — a consumer that only folds them
    (regret accumulation, scenario-mean reduction) never holds the full
    (S, J, P) tensor.
    """

    s0: int
    s1: int
    unit_cost: np.ndarray          # (s1 - s0, J, P)
    out: dict                      # per-cell cost decomposition, chunk-sized
    workload: np.ndarray           # (J,)
    timings: dict                  # {"synth": s, "eval": s, "overlap": bool}


def evaluate_grid_chunks(
    jobs: list[ChainJob],
    policies: Sequence[Policy],
    scenarios,
    r_total: int = 0,
    *,
    scenario_chunk: int | None = None,
    windows: str = "dealloc",
    selfowned: str = "prop12",
    early_start: bool = True,
    pool: str = "dedicated",
    availability: Callable | Sequence[Callable] | None = None,
    backend: str = "auto",
    plan_backend: str = "auto",
    mesh=None,
    overlap: bool | None = None,
) -> Iterator[GridChunk]:
    """Stream the grid evaluation one scenario chunk at a time.

    Same contract as :func:`evaluate_grid` (one grid plan, same backends,
    same per-scenario results), but yields ``GridChunk`` objects instead of
    assembling the (S, J, P) tensor — peak memory is chunk-sized. Between
    ``next()`` calls the caller may invoke ``source.observe(...)`` on an
    adaptive ``ScenarioStream``: the generator builds each chunk lazily
    AFTER the previous one was consumed, which is exactly the chunk
    boundary the adaptive adversary's feedback round-trip is defined at.

    ``mesh`` shards the scenario axis over a device mesh (jax backend
    only — see :func:`evaluate_grid`); ``overlap`` double-buffers chunk
    synthesis (default: on for non-numpy backends, off for reactive
    adaptive streams, whose chunks cannot be prefetched).

    Validation (and the plan build) runs EAGERLY at the call, not at the
    first ``next()`` — a bad ``scenario_chunk`` fails here, at the call
    site it names.
    """
    with span("prepare_stream"):
        source, gplan, backend, chunk, _, mesh, overlap = _prepare_stream(
            jobs, policies, scenarios, r_total, windows, selfowned, pool,
            availability, backend, plan_backend, scenario_chunk, mesh,
            overlap)

    def _iter():
        J, P = gplan.n_jobs, gplan.n_policies
        wl = np.maximum(gplan.workload, 1e-12)
        fresh = lambda s0, s1: {k: np.zeros((s1 - s0, J, P))
                                for k in _OUT_KEYS}
        for s0, s1, out, synth_t, eval_t in _chunk_loop(
                source, gplan, backend, chunk, mesh, overlap, early_start,
                fresh):
            unit = (out["spot_cost"] + out["ondemand_cost"]) \
                / wl[None, :, None]
            yield GridChunk(s0=s0, s1=s1, unit_cost=unit, out=out,
                            workload=gplan.workload.copy(),
                            timings={"synth": synth_t, "eval": eval_t,
                                     "overlap": overlap})

    return _iter()


def evaluate_grid(
    jobs: list[ChainJob],
    policies: Sequence[Policy],
    scenarios,
    r_total: int = 0,
    *,
    windows: str = "dealloc",
    selfowned: str = "prop12",
    early_start: bool = True,
    pool: str = "dedicated",
    availability: Callable | Sequence[Callable] | None = None,
    backend: str = "auto",
    plan_backend: str = "auto",
    scenario_chunk: int | None = None,
    reduce: str = "stack",
    mesh=None,
    overlap: bool | None = None,
) -> EngineResult:
    """Evaluate every job under every policy in every market scenario.

    Returns an ``EngineResult`` whose ``unit_cost[s]`` is the (J, P) TOLA
    cost matrix for scenario s; per-cell cost decompositions and per-policy
    self-owned stats ride along. ``scenarios`` may be one ``SpotMarket``, a
    sequence of scenario markets sharing a slot grid, or a declarative
    ``ScenarioSpec`` / ``ScenarioStream`` (see ``engine.scenarios``) whose
    price paths are synthesized on demand — on device for the jax/pallas
    backends, with no per-scenario Python objects on the hot path.

    ``scenario_chunk=K`` evaluates the scenario axis K scenarios per pass
    against one shared grid plan (chunk results are bit-identical to the
    monolithic pass — chunking changes memory, not arithmetic);
    ``reduce="mean"`` folds the chunks into the scenario-mean cost tensor
    (shape (1, J, P), ``n_scenarios_total`` keeps S) so peak host memory is
    independent of S. ``timings["synth"]`` reports scenario-synthesis
    seconds and ``timings["chunks"]`` the per-chunk split.

    ``pool`` selects the self-owned semantics: "dedicated" is the
    counterfactual evaluator (TOLA / Alg. 4 scoring, optionally against a
    realized ``availability`` query — one callable, or a list of S
    per-scenario callables for scenario-batched pool refinement, in which
    case the self-owned stats gain a leading scenario axis and the batch
    cannot be chunked), "shared" replays the chronological shared-pool
    allocation per policy (fixed-policy sweep semantics of ``run_jobs``).
    ``plan_backend`` selects where the plan tensors are built (see
    :func:`resolve_plan_backend`); ``timings["plan_device"]`` reports the
    device-build seconds (0.0 on the host plan path).

    ``mesh`` shards the SCENARIO axis across a device mesh (DESIGN.md §9):
    pass a ``GridMesh``, an int shard count (at most the visible
    devices), or a jax ``Mesh`` with a ``"data"`` axis.
    Mesh evaluation is a jax-backend feature ("auto" resolves to jax;
    numpy/pallas raise) — each shard synthesizes and scores only its own
    scenario slice, with no cross-device traffic in the compiled programs;
    a chunk whose scenario count is not divisible by the shard count is
    padded (last scenario repeated) and sliced back before results reach
    the caller, so results are independent of the mesh size (1-device mesh
    bitwise-identical to unsharded jax). ``overlap`` double-buffers chunk
    synthesis on the device paths: chunk k+1's synthesis is dispatched
    (async) before chunk k's evaluation blocks. Default: on for non-numpy
    backends, forced off for reactive adaptive streams (their chunks
    cannot be prefetched); ``timings["overlap"]`` records the resolved
    flag, and the per-chunk ``synth`` entries then measure the RESIDUAL
    wait, not the full synthesis time.
    """
    if reduce not in _REDUCES:
        raise ValueError(f"unknown reduce {reduce!r}; pick from {_REDUCES}")
    if reduce == "mean" and isinstance(availability, (list, tuple)):
        raise ValueError("reduce='mean' cannot fold per-scenario "
                         "availability results; use reduce='stack'")
    with span("evaluate_grid", reduce=reduce) as root:
        with span("prepare_stream"):
            source, gplan, backend, chunk, single, mesh, overlap = \
                _prepare_stream(
                    jobs, policies, scenarios, r_total, windows, selfowned,
                    pool, availability, backend, plan_backend,
                    scenario_chunk, mesh, overlap)
        S, J, P = source.n_scenarios, gplan.n_jobs, gplan.n_policies
        root.set(backend=backend, scenarios=S, overlap=overlap)

        if reduce == "stack":
            # The backend writes straight into slices of the (S, J, P)
            # tensors: no chunk tensor, no copy.
            out = {k: np.zeros((S, J, P)) for k in _OUT_KEYS}
            out_for = lambda s0, s1: {k: v[s0:s1] for k, v in out.items()}
        else:
            acc = {k: np.zeros((J, P)) for k in _OUT_KEYS}
            buf = {k: np.zeros((chunk, J, P)) for k in _OUT_KEYS}
            out_for = lambda s0, s1: {k: v[:s1 - s0] for k, v in buf.items()}
        chunk_timings: list[dict] = []
        synth_total = eval_total = 0.0
        for s0, s1, out_chunk, synth_t, eval_t in _chunk_loop(
                source, gplan, backend, chunk, mesh, overlap, early_start,
                out_for):
            if reduce == "mean":
                for k in _OUT_KEYS:
                    acc[k] += out_chunk[k].sum(axis=0)
            synth_total += synth_t
            eval_total += eval_t
            chunk_timings.append({"scenarios": [s0, s1], "synth": synth_t,
                                  "eval": eval_t})
        if reduce == "mean":
            out = {k: v[None] / S for k, v in acc.items()}

    per_scenario = gplan.per_scenario
    so_shape = (S, J, P) if per_scenario else (J, P)
    selfowned_work = np.zeros(so_shape)
    selfowned_reserved = np.zeros(so_shape)
    for g in gplan.groups:
        sw = np.asarray(g.selfowned_work)
        sr = np.asarray(g.selfowned_reserved)
        if per_scenario and not g.per_scenario:
            sw, sr = np.broadcast_to(sw, (S, J)), np.broadcast_to(sr, (S, J))
        selfowned_work[..., g.policy_idx] = sw[..., None]
        selfowned_reserved[..., g.policy_idx] = sr[..., None]

    # Delta-evaluation handle: recorded whenever the inputs have a
    # cross-call identity (fingerprintable scenarios, no availability
    # queries) and the full (S, J, P) stack is present to splice from.
    delta_state = None
    if reduce == "stack" and availability is None \
            and gplan.group_keys is not None:
        from repro.engine import cache as _cache
        sfp = _cache.scenario_fingerprint(scenarios)
        if sfp is not None:
            delta_state = {
                "jobs_fp": gplan.jobs_fp,
                "scenario_fp": sfp,
                "n_scenarios": S,
                "config": {"r_total": float(r_total), "windows": windows,
                           "selfowned": selfowned, "pool": pool,
                           "early_start": bool(early_start),
                           "backend": backend,
                           "plan_backend": gplan.plan_backend},
                "group_rep": {key: int(g.policy_idx[0])
                              for key, g in zip(gplan.group_keys,
                                                gplan.groups)},
            }

    total = out["spot_cost"] + out["ondemand_cost"]
    unit = total / np.maximum(gplan.workload, 1e-12)[None, :, None]
    return EngineResult(
        unit_cost=unit,
        spot_cost=out["spot_cost"],
        ondemand_cost=out["ondemand_cost"],
        spot_work=out["spot_work"],
        ondemand_work=out["ondemand_work"],
        workload=gplan.workload.copy(),
        selfowned_work=selfowned_work,
        selfowned_reserved=selfowned_reserved,
        backend=backend,
        single_market=single and reduce == "stack",
        n_scenarios_total=S,
        # plan_device: the jit plan-build seconds alone — on the staged
        # device path the pool phase is dominated by HOST work (the
        # availability-query callables), which must not masquerade as
        # device-build time.
        timings={"plan": gplan.plan_seconds, "pool": gplan.pool_seconds,
                 "eval": eval_total, "synth": synth_total,
                 "chunks": chunk_timings, "overlap": overlap,
                 "plan_cached": gplan.plan_cached,
                 "plan_device": (gplan.plan_seconds
                                 if gplan.device else 0.0)},
        obs=maybe_snapshot(),
        delta_state=delta_state,
    )
