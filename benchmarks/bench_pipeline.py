"""End-to-end pipeline throughput: jobs -> plans -> pool -> cost tensor.

``bench_engine`` times only the backend evaluation of a prebuilt grid plan;
this benchmark times the WHOLE ``evaluate_grid`` pass per backend — plan
tensor construction, self-owned pool arithmetic, and market realization —
and breaks the wall time into those three phases (``EngineResult.timings``),
so the plan layer's cost is a tracked number instead of hidden warmup.
It also races the batched plan builder (``build_plans_batch``, one
vectorized (G, J, L) pass over the deduplicated window-parameter grid)
against the legacy per-group ``build_plans`` loop it replaced, and — for
every non-numpy backend — the HOST plan path (f64 numpy oracle) against
the DEVICE plan path (``plan_backend="device"``: the whole jobs->plan
tensor pass as one jit program, ``<backend>+device-plan`` entries).

Cross-call reuse legs (DESIGN.md §11, run FIRST so the cold numbers are
honest):

* ``jax+warm`` — the identical ``evaluate_grid`` call twice in one
  process: the first pays every XLA compile and plan build, the second
  must hit the cross-call plan cache on every group and compile nothing
  (both counted, via the plan-cache counters and ``CompileWatch``); the
  cache-smoke CI job gates hit-rate == 100%, warm compiles == 0, and the
  cold/warm speedup.
* ``jax+delta`` — ~10% of the grid re-bid, re-scored through
  ``evaluate_grid_delta`` against the warm result; records how many eval
  groups were actually re-scored and the max deviation from a full
  re-eval.

Scenario legs (the stream side of the pipeline):

* ``scenario_synthesis`` — price-path construction throughput, host
  materialized list (``make_scenarios``, one numpy Generator + SpotMarket
  per scenario) vs declarative ``ScenarioSpec`` (counter-hash synthesis:
  f64 oracle rows, and the jitted device generator when jax is present),
  S swept geometrically up to ``--scenario-sweep-max`` (default 4096) over
  the same horizon as the grid.
* ``<backend>+spec-stream`` — the full end-to-end pass from a
  ``ScenarioSpec`` with ``scenario_chunk`` (chunked device synthesis +
  evaluation against one shared grid plan), gated in CI with the same
  2x per-cell regression rule as the other legs; its cost tensor is
  cross-checked against the numpy oracle on the SAME spec.
* ``jax+shard`` / ``jax+shard+overlap`` — the spec-stream workload with
  the scenario axis sharded over a device mesh (DESIGN.md §9; ``--mesh``
  shards, default every visible device), without and with double-buffered
  chunk synthesis; both cross-checked against the same numpy spec oracle.
  The ``shard_scaling`` sweep then streams regret curves through
  ``replay_stream`` at geometrically growing S (up to
  ``--shard-scale-max``) on a reduced grid — peak memory stays
  chunk-sized no matter how large S grows, which is the point.
* ``jax+shard2d`` — the same workload on the 2-D scenario x policy-group
  ``GridMesh`` (``--mesh2d NxM``; default splits the visible devices
  N//2 x 2), so the eval-group axis shards over ``"model"`` next to the
  scenario axis over ``"data"``.

Refinement legs (``--only refine``; TOLA pool-refinement rounds, the
per-scenario-availability path of DESIGN.md §9):

* ``jax+refine`` — ``run_tola_scenarios`` with ``pool_iters`` refinement
  rounds, ONE batched per-scenario-availability engine pass per round,
  raced against a loop of single-market (S = 1) ``run_tola_scenarios``
  calls (one engine call per scenario per round, same results to f32
  tolerance);
  ``refine_batch_speedup`` is a same-machine ratio with a modest CI
  floor — the engine pass batches but the learner replay between rounds
  is identical host work in both paths, so Amdahl caps the end-to-end
  ratio well below the engine-only win.
* ``jax+refine+shard`` — the same batched refinement on the 2-D mesh;
  ``refine_shard_speedup`` is recorded honestly (forced host devices
  SPLIT the visible cores, so on a small CPU box expect ~1x — like the
  other shard legs, the CI gate is the 2x per-cell regression rule vs
  the committed JSON plus bit-parity, not an absolute speedup; the
  absolute win needs real multi-device hardware).

``--only {warm,plan,e2e,stream,synth,shard,refine}`` runs a subset of
those sections (default: all).

Emits ``BENCH_pipeline.json``:

    PYTHONPATH=src python -m benchmarks.bench_pipeline \
        [--jobs 512] [--policies 70] [--scenarios 4] [--r 600] \
        [--backends numpy jax] [--out BENCH_pipeline.json]

Off-TPU the pallas backend runs in interpret mode — kernel-logic timing,
not TPU speed (tagged in the output; compare numpy vs jax there). The
shard legs on a 1-device box are the degenerate mesh — run CI-style with
XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise real
sharding on CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

import numpy as np

from repro import obs
from repro.core import Policy, generate_chain_jobs, selfowned_policies
from repro.core.scheduler import build_plans, build_plans_batch
from repro.engine import ScenarioSpec, evaluate_grid, make_scenarios
from repro.engine.plan import distinct_window_params
from benchmarks.bench_engine import obs_block

__all__ = ["run", "main"]


def _best_of(fn, iters: int) -> float:
    best = np.inf
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _synth_sweep(horizon: float, n_scenarios: int, sweep_max: int,
                 seed: int, iters: int) -> dict:
    """Scenario-synthesis throughput: host list vs spec (numpy / device)."""
    try:
        import jax
        has_jax = True
    except Exception:
        has_jax = False
    from repro.engine.scenarios import SynthBatch, _device_synth_fn

    sweep = []
    S = max(n_scenarios, 64)
    sizes = []
    while S <= sweep_max:
        sizes.append(S)
        S *= 4
    for S in sizes:
        spec = ScenarioSpec("fresh", horizon, S, seed=seed + 1000)
        cells = S * spec.n_slots
        it = 1 if S > 1024 else iters   # the big host lists take seconds
        t_list = _best_of(
            lambda: make_scenarios(horizon, S, seed=seed + 1000), it)
        t_spec = _best_of(lambda: spec.prices(), it)
        entry = {"S": S, "n_slots": spec.n_slots, "cells": cells,
                 "host_list_seconds": t_list,
                 "spec_numpy_seconds": t_spec,
                 "spec_numpy_speedup": t_list / t_spec}
        msg = (f"[synth S={S:5d}] list {t_list:7.3f}s  "
               f"spec {t_spec:7.3f}s ({t_list / t_spec:.1f}x)")
        if has_jax:
            def dev():
                SynthBatch(spec, 0, S, device=True).prepare()

            dev()                        # absorb the jit compile
            entry["spec_device_seconds"] = _best_of(dev, it)
            entry["spec_device_speedup"] = (t_list
                                            / entry["spec_device_seconds"])
            msg += (f"  device {entry['spec_device_seconds']:7.3f}s "
                    f"({entry['spec_device_speedup']:.1f}x)")
            _device_synth_fn.cache_clear()  # free the big per-S programs
        sweep.append(entry)
        print(msg)
    return {"kind": "fresh", "sweep": sweep}


SECTIONS = ("warm", "plan", "e2e", "stream", "synth", "shard", "refine")


def _parse_mesh2d(mesh2d: str | None):
    """``"NxM"`` -> a 2-D GridMesh; None -> N//2 x 2 over visible devices.

    Degenerates to 1x1 (the unsharded-equivalent mesh) on a 1-device box,
    so the legs always run; CI forces 8 host devices and passes the 4x2 /
    2x4 matrix explicitly.
    """
    from repro.engine import GridMesh

    if mesh2d is not None:
        n, _, m = mesh2d.lower().partition("x")
        return GridMesh.create(int(n), model_devices=int(m or 1))
    import jax

    avail = len(jax.devices())
    m = 2 if avail >= 2 else 1
    return GridMesh.create(max(avail // m, 1), model_devices=m)


def _warm_section(out, jobs, grid, horizon, n_scenarios, r_total, cells,
                  seed):
    """Cross-call reuse legs (DESIGN.md §11): cold/warm/delta evaluate_grid.

    Runs FIRST among the jax-touching sections so the cold call genuinely
    pays every XLA compile of the process; the warm call (same
    jobs/spec/grid, same process) must then hit the plan cache on every
    group and compile nothing — the cache-smoke CI job gates on exactly
    these numbers. The jax persistent compilation cache is deliberately
    NOT wired up here (it would hollow out the cold leg).
    """
    import dataclasses

    from repro.engine import cache as engine_cache
    from repro.engine import evaluate_grid_delta
    from repro.obs.compiled import CompileWatch

    spec = ScenarioSpec("fresh", horizon, n_scenarios, seed=seed + 1000)
    engine_cache.clear_caches()

    watch = CompileWatch()
    with watch:
        t0 = time.perf_counter()
        res_cold = evaluate_grid(jobs, grid, spec, r_total, backend="jax")
        cold = time.perf_counter() - t0
    cold_compiles = watch.compiles

    pc0 = engine_cache.PLAN_CACHE.cache_info()
    with watch:
        t0 = time.perf_counter()
        res_warm = evaluate_grid(jobs, grid, spec, r_total, backend="jax")
        warm = time.perf_counter() - t0
    pc1 = engine_cache.PLAN_CACHE.cache_info()
    hits, misses = pc1.hits - pc0.hits, pc1.misses - pc0.misses
    entry = {
        "cold_end_to_end_seconds": cold,
        "end_to_end_seconds": warm,
        "warm_speedup": cold / warm,
        "cold_compiles": cold_compiles,
        "warm_compiles": watch.compiles,
        "compile_watch_supported": watch.supported,
        "plan_cache_hits": hits,
        "plan_cache_misses": misses,
        "plan_cache_hit_rate": hits / max(hits + misses, 1),
        "plan_cached_groups": res_warm.timings.get("plan_cached", 0),
        "cells_per_sec_end_to_end": cells / warm,
        "max_abs_diff_vs_cold": float(
            np.abs(res_warm.unit_cost - res_cold.unit_cost).max()),
    }
    out["backends"]["jax+warm"] = entry
    print(f"[jax+warm        ] cold {cold:7.3f}s ({cold_compiles} compiles)"
          f"  warm {warm:7.3f}s ({watch.compiles} compiles, "
          f"{hits}/{hits + misses} plan-cache hits)  "
          f"{entry['warm_speedup']:.1f}x")

    # ~10% of the grid gets perturbed bids -> new eval groups; the delta
    # path re-scores only those and splices everything else straight out
    # of res_warm's tensors.
    idx = list(range(0, len(grid), 10))
    grid2 = list(grid)
    for k, i in enumerate(idx):
        grid2[i] = dataclasses.replace(
            grid[i], bid=grid[i].bid * 1.01 + 1e-4 * (k + 1))
    # Full re-eval FIRST: it pays the XLA compiles for the new bids'
    # batch shapes, so the delta timing below measures the work saved by
    # re-scoring fewer groups, not a compile-order artifact.
    t0 = time.perf_counter()
    res_full = evaluate_grid(jobs, grid2, spec, r_total, backend="jax")
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_delta = evaluate_grid_delta(res_warm, jobs, grid2, spec, r_total,
                                    backend="jax")
    t_delta = time.perf_counter() - t0
    dentry = {
        "end_to_end_seconds": t_delta,
        "full_end_to_end_seconds": t_full,
        "delta_speedup": t_full / t_delta,
        "n_policies_changed": len(idx),
        "delta_groups_rescored": int(
            res_delta.timings["delta_groups_rescored"]),
        "delta_groups_total": int(res_delta.timings["delta_groups_total"]),
        "max_abs_diff_vs_full": float(
            np.abs(res_delta.unit_cost - res_full.unit_cost).max()),
    }
    out["backends"]["jax+delta"] = dentry
    print(f"[jax+delta       ] {t_delta:7.3f}s re-scoring "
          f"{dentry['delta_groups_rescored']}/{dentry['delta_groups_total']} "
          f"groups (full {t_full:7.3f}s, {dentry['delta_speedup']:.1f}x, "
          f"max diff {dentry['max_abs_diff_vs_full']:.2e})")


def run(n_jobs: int, n_policies: int, n_scenarios: int, r_total: int,
        backends: list[str], seed: int = 0, job_type: int = 2,
        iters: int = 3, scenario_sweep_max: int = 4096,
        sections=None, mesh: int | None = None,
        shard_scale_max: int = 65536, mesh2d: str | None = None,
        pool_iters: int = 2) -> dict:
    if iters < 1:
        raise ValueError("need --iters >= 1 (one timed pass after warmup)")
    sections = SECTIONS if sections is None else tuple(sections)
    for s in sections:
        if s not in SECTIONS:
            raise ValueError(f"unknown section {s!r}; pick from {SECTIONS}")
    jobs = generate_chain_jobs(n_jobs, job_type, seed=seed)
    horizon = max(j.deadline for j in jobs) + 1.0
    markets = make_scenarios(horizon, n_scenarios, seed=seed + 1000)
    grid = selfowned_policies()[:n_policies]
    if len(grid) < n_policies:
        raise ValueError(f"policy grid has only {len(grid)} policies")
    cells = n_scenarios * n_jobs * len(grid)

    # --- plan phase: batched builder vs the legacy per-group loop --------
    xs = list(distinct_window_params(grid, r_total).values())

    out = {
        "n_jobs": n_jobs,
        "n_policies": len(grid),
        "n_scenarios": n_scenarios,
        "r_total": r_total,
        "job_type": job_type,
        "seed": seed,
        "cells": cells,
        "window_groups": len(xs),
        "backends": {},
    }
    try:
        import jax
        out["jax_backend"] = jax.default_backend()
    except Exception:
        out["jax_backend"] = None

    # Metrics collect across every leg; compiled programs are captured on
    # the warmup pass of each leg (capture lowers+compiles once, which
    # must not count against the timed iterations). Both land in
    # out["obs"] — the enriched phase/collective breakdown.
    reg = obs.CompiledRegistry()
    _obs_stack = contextlib.ExitStack()
    _obs_stack.enter_context(obs.METRICS.collecting(reset=True))

    if "warm" in sections:
        if out["jax_backend"] is None or "jax" not in backends:
            print("[warm   ] skipped (needs jax and the jax backend)")
        else:
            _warm_section(out, jobs, grid, horizon, n_scenarios, r_total,
                          cells, seed)

    if "plan" in sections:
        t_loop = _best_of(
            lambda: [build_plans(jobs, Policy(beta=x, bid=0.0), r_total)
                     for x in xs], iters)
        t_batch = _best_of(lambda: build_plans_batch(jobs, xs), iters)
        out["plan_loop_seconds"] = t_loop
        out["plan_batch_seconds"] = t_batch
        out["plan_batch_speedup"] = t_loop / t_batch
        print(f"[plan  ] loop {t_loop:7.3f}s  batch {t_batch:7.3f}s  "
              f"({out['plan_batch_speedup']:.1f}x, {len(xs)} window groups)")

    # --- end-to-end jobs -> cost tensor, per (backend, plan-backend) -----
    # Host-plan legs keep the bare backend key (the CI regression gate
    # compares them across runs); the device-plan leg of each non-numpy
    # backend races the SAME end-to-end pass with the plan tensors built
    # on device ("<backend>+device-plan").
    legs = [(b, "host") for b in backends]
    legs += [(b, "device") for b in backends if b != "numpy"]
    if "e2e" not in sections:
        legs = []
    ref = None
    for backend, plan_backend in legs:
        name = backend if plan_backend == "host" \
            else f"{backend}+device-plan"
        res = None
        best = np.inf
        phases = None
        for it in range(iters + 1):
            cap = obs.capture(reg) if it == 0 else contextlib.nullcontext()
            t0 = time.perf_counter()
            with cap:
                res = evaluate_grid(jobs, grid, markets, r_total,
                                    backend=backend,
                                    plan_backend=plan_backend)
            dt = time.perf_counter() - t0
            if it == 0:
                warmup = dt      # absorbs jit / pallas compilation
            elif dt < best:
                best, phases = dt, dict(res.timings)
        entry = {
            "end_to_end_seconds": best,
            "warmup_seconds": warmup,
            "cells_per_sec_end_to_end": cells / best,
            "plan_seconds": phases["plan"],
            "pool_seconds": phases["pool"],
            "eval_seconds": phases["eval"],
            # timings is always fully populated now (span-derived; the
            # .get guard predates the empty-dict default of EngineResult)
            "synth_seconds": phases["synth"],
            "plan_device_seconds": phases["plan_device"],
            "interpret": backend == "pallas"
            and out["jax_backend"] == "cpu",
        }
        if entry["interpret"]:
            entry["note"] = ("pallas kernels ran in INTERPRET mode on CPU — "
                             "kernel-logic timing, NOT TPU speed; do not "
                             "compare against the numpy/jax entries")
        out["backends"][name] = entry
        if ref is None:
            ref = res.unit_cost
            entry["max_abs_diff_vs_first"] = 0.0
        else:
            entry["max_abs_diff_vs_first"] = float(
                np.abs(res.unit_cost - ref).max())
        tag = "  (interpret — kernel logic, NOT TPU speed)" \
            if entry["interpret"] else ""
        print(f"[{name:16s}] {best:7.3f}s end-to-end  "
              f"(plan {phases['plan']:.3f}  pool {phases['pool']:.3f}  "
              f"eval {phases['eval']:.3f})  "
              f"{cells / best / 1e3:9.1f}k cells/s{tag}")

    # --- chunked scenario stream from a declarative spec -----------------
    # Same grid, but the scenarios come from a ScenarioSpec streamed
    # scenario_chunk per pass (device-synthesized price paths on the
    # non-numpy backends). Cross-checked against the numpy oracle on the
    # SAME spec (the list-path ref above realizes different prices).
    spec = ScenarioSpec("fresh", horizon, n_scenarios, seed=seed + 1000)
    chunk = max(1, n_scenarios // 2)
    spec_ref = None
    if "stream" in sections or "shard" in sections:
        spec_ref = evaluate_grid(jobs, grid, spec, r_total,
                                 backend="numpy").unit_cost

    def stream_leg(name, backend, smesh=None, overlap=None):
        res = None
        best = np.inf
        phases = None
        for it in range(iters + 1):
            cap = obs.capture(reg) if it == 0 else contextlib.nullcontext()
            t0 = time.perf_counter()
            with cap:
                res = evaluate_grid(jobs, grid, spec, r_total,
                                    backend=backend, scenario_chunk=chunk,
                                    mesh=smesh, overlap=overlap)
            dt = time.perf_counter() - t0
            if it == 0:
                warmup = dt
            elif dt < best:
                best, phases = dt, dict(res.timings)
        entry = {
            "end_to_end_seconds": best,
            "warmup_seconds": warmup,
            "cells_per_sec_end_to_end": cells / best,
            "plan_seconds": phases["plan"],
            "pool_seconds": phases["pool"],
            "eval_seconds": phases["eval"],
            "synth_seconds": phases["synth"],
            "plan_device_seconds": phases["plan_device"],
            "scenario_chunk": chunk,
            "n_chunks": len(phases["chunks"]),
            "overlap": bool(phases["overlap"]),
            "interpret": backend == "pallas"
            and out["jax_backend"] == "cpu",
            "max_abs_diff_vs_numpy_spec": float(
                np.abs(res.unit_cost - spec_ref).max()),
        }
        if smesh is not None:
            entry["mesh_shards"] = smesh.n_shards
        if entry["interpret"]:
            entry["note"] = ("pallas kernels ran in INTERPRET mode on CPU — "
                             "kernel-logic timing, NOT TPU speed; do not "
                             "compare against the numpy/jax entries")
        out["backends"][name] = entry
        print(f"[{name:17s}] {best:7.3f}s end-to-end  "
              f"(plan {phases['plan']:.3f}  synth {phases['synth']:.3f}  "
              f"eval {phases['eval']:.3f}, {len(phases['chunks'])} chunks)  "
              f"{cells / best / 1e3:9.1f}k cells/s")
        return entry

    if "stream" in sections:
        for backend in [b for b in backends if b != "numpy"]:
            stream_leg(f"{backend}+spec-stream", backend)

    if "synth" in sections:
        out["scenario_synthesis"] = _synth_sweep(
            horizon, n_scenarios, scenario_sweep_max, seed, iters)

    if "shard" in sections:
        if out["jax_backend"] is None or "jax" not in backends:
            print("[shard  ] skipped (needs jax and the jax backend)")
        else:
            _shard_section(out, jobs, grid, stream_leg, mesh,
                           shard_scale_max, r_total, horizon, seed,
                           job_type, reg, mesh2d)

    if "refine" in sections:
        if out["jax_backend"] is None or "jax" not in backends:
            print("[refine ] skipped (needs jax and the jax backend)")
        elif r_total <= 0:
            print("[refine ] skipped (needs --r > 0 for pool refinement)")
        else:
            _refine_section(out, jobs, grid, markets, r_total, seed,
                            pool_iters, iters, mesh2d, reg)
    _obs_stack.close()
    out["obs"] = obs_block(reg)
    return out


def _shard_section(out, jobs, grid, stream_leg, mesh, shard_scale_max,
                   r_total, horizon, seed, job_type, reg, mesh2d=None):
    """Sharded spec-stream legs + the replay_stream scenario-scaling sweep.

    The sweep runs on a REDUCED grid (its point is the scenario axis, not
    the cell count): regret statistics for S up to ``shard_scale_max``
    scenarios streamed ``chunk`` at a time through the sharded engine +
    sharded fold — wall clock grows linearly in S while peak memory stays
    pinned at one chunk.
    """
    from repro.engine import GridMesh
    from repro.engine.mesh import as_scenario_mesh
    from repro.learn import replay_stream

    smesh = as_scenario_mesh(mesh)
    if smesh is None:
        smesh = GridMesh.create()
    plain = stream_leg("jax+shard", "jax", smesh=smesh, overlap=False)
    over = stream_leg("jax+shard+overlap", "jax", smesh=smesh, overlap=True)
    # The overlap win: residual synth wait once chunk k+1 is dispatched
    # before chunk k's eval blocks (see EngineResult.timings "overlap").
    over["overlap_synth_win_seconds"] = (plain["synth_seconds"]
                                         - over["synth_seconds"])

    # 2-D scenario x policy-group grid (DESIGN.md Section 9): the same
    # stream workload with the eval-group axis sharded over "model".
    gmesh = _parse_mesh2d(mesh2d)
    e2d = stream_leg("jax+shard2d", "jax", smesh=gmesh, overlap=False)
    e2d["mesh_shape"] = [gmesh.data_shards, gmesh.model_shards]

    chunk = 8192
    sw_jobs = generate_chain_jobs(16, job_type, seed=seed)
    sw_horizon = max(j.deadline for j in sw_jobs) + 1.0
    sw_grid = grid[:4]
    sweep = []
    S = chunk
    while S <= shard_scale_max:
        spec = ScenarioSpec("fresh", sw_horizon, S, seed=seed + 1)
        # First sweep point doubles as the capture pass for the sharded
        # fold program (its one-psum-per-chunk collective count belongs in
        # the obs block); its wall clock absorbs the capture's compile.
        cap = obs.capture(reg) if not sweep else contextlib.nullcontext()
        t0 = time.perf_counter()
        with cap:
            slr = replay_stream(sw_jobs, sw_grid, spec, r_total,
                                learners=["hedge"], seed=seed,
                                scenario_chunk=chunk, backend="jax",
                                engine_backend="jax", mesh=smesh,
                                overlap=True)
        dt = time.perf_counter() - t0
        sweep.append({
            "S": S, "seconds": dt, "scenarios_per_sec": S / dt,
            "n_chunks": slr.n_chunks,
            "regret": float(slr.regret_per_job()[0]),
            "regret_std": float(slr.regret_std()[0]),
        })
        print(f"[shard scale S={S:8d}] {dt:8.2f}s  "
              f"{S / dt:8.0f} scenarios/s  {slr.n_chunks:4d} chunks  "
              f"regret {sweep[-1]['regret']:.4f} "
              f"+- {sweep[-1]['regret_std']:.4f}")
        if S >= shard_scale_max:
            break
        S = min(S * 4, shard_scale_max)  # always land on the cap itself
    out["shard_scaling"] = {
        "mesh_shards": smesh.n_shards, "scenario_chunk": chunk,
        "n_jobs": len(sw_jobs), "n_policies": len(sw_grid),
        "sweep": sweep,
    }


def _refine_section(out, jobs, grid, markets, r_total, seed, pool_iters,
                    iters, mesh2d, reg):
    """TOLA pool-refinement legs: per-scenario loop vs batched vs sharded.

    ``run_tola_scenarios`` makes exactly ONE per-scenario-availability
    engine pass per refinement round; the loop baseline calls it once per
    market at S = 1 (one engine call per scenario per round, same results
    to f32 tolerance).
    ``refine_batch_speedup`` is a same-machine ratio with a modest CI
    floor (the per-round learner replay is identical host work in both
    paths, so Amdahl caps the end-to-end ratio). The sharded leg rides
    the 2-D GridMesh through EVERY round (refined per-scenario plan
    stacks on "data", group rows on "model"); its speedup is recorded
    honestly and gated only by the per-cell regression rule plus
    bit-parity with the batched leg — forced host devices share the
    visible cores, so the absolute shard win needs real multi-device
    hardware.
    """
    from repro.core import run_tola_scenarios

    S = len(markets)
    kw = dict(r_total=r_total, pool_iters=pool_iters, backend="jax")
    rounds = 1 + pool_iters
    cells = S * len(jobs) * len(grid) * rounds

    def solo(s):
        return run_tola_scenarios(jobs, grid, [markets[s]], seed=seed + s,
                                  **kw)[0]

    def loop():
        return [solo(s) for s in range(S)]

    solo(0)  # absorb S=1 compiles
    t0 = time.perf_counter()
    res_loop = loop()
    t_loop = time.perf_counter() - t0

    def timed(fn, capture_first):
        best, res = np.inf, None
        for it in range(iters + 1):
            cap = obs.capture(reg) if it == 0 and capture_first \
                else contextlib.nullcontext()
            t0 = time.perf_counter()
            with cap:
                res = fn()
            dt = time.perf_counter() - t0
            if it == 0:
                warmup = dt
            else:
                best = min(best, dt)
        return best, warmup, res

    t_batch, warm_b, res_batch = timed(
        lambda: run_tola_scenarios(jobs, grid, markets, seed=seed, **kw),
        capture_first=False)
    diff_loop = max(
        float(np.abs(rb.cost_matrix - rl.cost_matrix).max())
        for rb, rl in zip(res_batch, res_loop))
    entry = {
        "end_to_end_seconds": t_batch,
        "warmup_seconds": warm_b,
        "loop_seconds": t_loop,
        "refine_batch_speedup": t_loop / t_batch,
        "pool_iters": pool_iters,
        "refine_rounds": rounds,
        "n_scenarios": S,
        "refine_cells": cells,
        "cells_per_sec_end_to_end": cells / t_batch,
        "max_abs_diff_vs_loop": diff_loop,
        "note": ("end-to-end includes the per-round host learner replay, "
                 "identical in both paths — the batched win is in the "
                 "engine pass, Amdahl caps the e2e ratio"),
    }
    out["backends"]["jax+refine"] = entry
    print(f"[jax+refine      ] {t_batch:7.3f}s batched "
          f"({rounds} rounds x 1 engine pass)  loop {t_loop:7.3f}s "
          f"({S * rounds} passes)  {entry['refine_batch_speedup']:.1f}x  "
          f"max diff {diff_loop:.2e}")

    gmesh = _parse_mesh2d(mesh2d)
    t_shard, warm_s, res_shard = timed(
        lambda: run_tola_scenarios(jobs, grid, markets, seed=seed,
                                   mesh=gmesh, **kw),
        capture_first=True)   # captures the chain_ps/task_ps:sharded HLO
    diff_shard = max(
        float(np.abs(rs.cost_matrix - rb.cost_matrix).max())
        for rs, rb in zip(res_shard, res_batch))
    sentry = {
        "end_to_end_seconds": t_shard,
        "warmup_seconds": warm_s,
        "refine_shard_speedup": t_batch / t_shard,
        "mesh_shards": gmesh.n_shards,
        "mesh_shape": [gmesh.data_shards, gmesh.model_shards],
        "pool_iters": pool_iters,
        "refine_rounds": rounds,
        "n_scenarios": S,
        "refine_cells": cells,
        "cells_per_sec_end_to_end": cells / t_shard,
        "max_abs_diff_vs_batched": diff_shard,
        "note": ("forced host devices split the visible CPU cores, so "
                 "expect ~1x on a small box; the absolute shard win "
                 "needs real multi-device hardware"),
    }
    out["backends"]["jax+refine+shard"] = sentry
    print(f"[jax+refine+shard] {t_shard:7.3f}s on "
          f"{gmesh.data_shards}x{gmesh.model_shards} mesh "
          f"({sentry['refine_shard_speedup']:.2f}x vs batched)  "
          f"max diff {diff_shard:.2e}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--jobs", type=int, default=512)
    p.add_argument("--policies", type=int, default=70)
    p.add_argument("--scenarios", type=int, default=4)
    p.add_argument("--r", type=int, default=600)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--job-type", type=int, default=2)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--backends", nargs="+", default=["numpy", "jax"],
                   choices=["numpy", "jax", "pallas"])
    p.add_argument("--scenario-sweep-max", type=int, default=4096,
                   help="largest S of the scenario-synthesis sweep")
    p.add_argument("--only", nargs="+", default=None, choices=SECTIONS,
                   help="run a subset of the benchmark sections")
    p.add_argument("--mesh", type=int, default=None,
                   help="shard count of the jax+shard legs (default: every "
                        "visible device; at most the visible count)")
    p.add_argument("--mesh2d", default=None, metavar="NxM",
                   help="scenario x policy-group grid of the jax+shard2d "
                        "and jax+refine+shard legs, e.g. 4x2 (default: "
                        "N//2 x 2 over the visible devices)")
    p.add_argument("--pool-iters", type=int, default=2,
                   help="TOLA pool-refinement rounds of the refine legs")
    p.add_argument("--shard-scale-max", type=int, default=65536,
                   help="largest S of the sharded replay_stream scaling "
                        "sweep (the committed baseline uses 1048576)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="save a Chrome/Perfetto span trace of the run "
                        "(CI uploads this from the smoke grid)")
    p.add_argument("--out", default="BENCH_pipeline.json")
    args = p.parse_args(argv)
    tracer = obs.Tracer() if args.trace else None
    ctx = obs.tracing(tracer) if tracer is not None \
        else contextlib.nullcontext()
    with ctx:
        res = run(args.jobs, args.policies, args.scenarios, args.r,
                  args.backends, seed=args.seed, job_type=args.job_type,
                  iters=args.iters,
                  scenario_sweep_max=args.scenario_sweep_max,
                  sections=args.only, mesh=args.mesh,
                  shard_scale_max=args.shard_scale_max,
                  mesh2d=args.mesh2d, pool_iters=args.pool_iters)
    if tracer is not None:
        tracer.save(args.trace)
        print(f"wrote Perfetto trace ({len(tracer)} spans): {args.trace}")
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {args.out}")
    return res


if __name__ == "__main__":
    main()
