"""Benchmark driver — one experiment per paper table + the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--jobs N] [--skip ...]

Sections:
  exp1  Table 2  — spot+on-demand cost improvement (Greedy / Even)
  exp2  Table 3  — overall improvement with self-owned instances
  exp3  Tables 4+5 — policy (12) vs naive self-owned (+ utilization ratio)
  exp4  Table 6  — TOLA online learning
  engine          — evaluation-engine throughput (numpy vs jax vs pallas)
                    on a (512 jobs x 70 policies x 4 scenarios) grid; emits
                    BENCH_engine.json (see benchmarks/bench_engine.py for
                    how to read it — off-TPU the pallas number is interpret
                    mode, i.e. kernel logic, not TPU speed)
  pipeline        — END-TO-END jobs -> plans -> pool -> cost tensor per
                    backend with a plan/pool/eval phase split, plus the
                    batched-plan-builder vs per-group-loop race; emits
                    BENCH_pipeline.json (benchmarks/bench_pipeline.py)
  learn           — online-learning replay throughput (numpy oracle vs the
                    scan-compiled jax replay) across a learner x eta-grid
                    sweep over the same grid; emits BENCH_learn.json
                    (benchmarks/bench_learn.py)
  obs             — observability report for one representative grid: the
                    span-derived phase totals, the compiled-program
                    gflops/MB/collective table, and the metrics snapshot
                    (benchmarks/bench_obs.py; --trace saves the Perfetto
                    trace of that run)
  roofline        — per-(arch x shape) roofline terms from the compiled
                    dry-run (reads benchmarks/roofline_cache.json if the
                    dry-run sweep has been run; see launch/dryrun.py)

--trace PATH runs the WHOLE driver under the repro.obs span tracer and
saves one Chrome/Perfetto trace JSON covering every selected section
(load it at https://ui.perfetto.dev).

Every exp accepts --scenarios S / --scenario-kind / --backend to evaluate S
spot-market scenarios in one engine pass (S=1 = the paper's tables), and
--mesh N to shard the scenario axis over an N-way device mesh (jax
backend; N may not exceed the visible device count).
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--jobs", type=int, default=None,
                   help="jobs per stream (default: 1500; --quick: 300)")
    p.add_argument("--quick", action="store_true",
                   help="small streams / reduced grids for CI-speed runs")
    sections = ["exp1", "exp2", "exp3", "exp4", "engine", "pipeline",
                "learn", "obs", "roofline"]
    p.add_argument("--skip", nargs="*", default=[], metavar="SECTION")
    p.add_argument("--only", nargs="*", default=None, metavar="SECTION")
    p.add_argument("--mesh", type=int, default=None,
                   help="shard the exp1-4 scenario axis over an N-way "
                        "device mesh (forwarded as --mesh N)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="trace the whole run with the repro.obs span "
                        "tracer and save the Chrome/Perfetto JSON here")
    args = p.parse_args(argv)

    for flag, values in (("--only", args.only), ("--skip", args.skip)):
        unknown = [v for v in (values or []) if v not in sections]
        if unknown:
            p.error(f"{flag}: unknown section(s): {', '.join(unknown)}. "
                    f"Valid sections: {', '.join(sections)}")

    n_jobs = args.jobs or (300 if args.quick else 1500)
    types = [1, 2] if args.quick else [1, 2, 3, 4]
    rs = [300, 1200] if args.quick else [300, 600, 900, 1200]
    rs4 = [0, 600] if args.quick else [0, 300, 600, 900, 1200]

    def want(name: str) -> bool:
        if args.only is not None:
            return name in args.only
        return name not in args.skip

    mesh_args = [] if args.mesh is None else ["--mesh", str(args.mesh)]

    import contextlib

    from repro import obs

    tracer = obs.Tracer() if args.trace else None
    ctx = obs.tracing(tracer) if tracer is not None \
        else contextlib.nullcontext()

    t0 = time.time()
    with ctx:
        _sections(args, want, n_jobs, types, rs, rs4, mesh_args)
    if tracer is not None:
        tracer.save(args.trace)
        print(f"wrote Perfetto trace ({len(tracer)} spans): {args.trace}")
    print(f"\n[benchmarks total: {time.time() - t0:.1f}s]")


def _sections(args, want, n_jobs, types, rs, rs4, mesh_args):
    if want("exp1"):
        from benchmarks import exp1_spot_ondemand
        exp1_spot_ondemand.main(["--jobs", str(n_jobs),
                                 "--types", *map(str, types), *mesh_args])
    if want("exp2"):
        from benchmarks import exp2_self_owned
        exp2_self_owned.main(["--jobs", str(n_jobs),
                              "--types", *map(str, types),
                              "--r", *map(str, rs), *mesh_args])
    if want("exp3"):
        from benchmarks import exp3_policy12
        exp3_policy12.main(["--jobs", str(n_jobs),
                            "--types", *map(str, types),
                            "--r", *map(str, rs), *mesh_args])
    if want("exp4"):
        from benchmarks import exp4_online_learning
        exp4_online_learning.main(["--jobs", str(n_jobs),
                                   "--r", *map(str, rs4), *mesh_args])
    if want("engine"):
        from benchmarks import bench_engine
        if args.quick:
            bench_engine.main(["--jobs", "128", "--policies", "64",
                               "--scenarios", "2", "--iters", "1"])
        else:
            bench_engine.main([])
    if want("pipeline"):
        from benchmarks import bench_pipeline
        if args.quick:
            bench_pipeline.main(["--jobs", "128", "--policies", "64",
                                 "--scenarios", "2", "--iters", "1"])
        else:
            bench_pipeline.main([])
    if want("learn"):
        from benchmarks import bench_learn
        if args.quick:
            bench_learn.main(["--jobs", "128", "--policies", "64",
                              "--scenarios", "2", "--iters", "1"])
        else:
            bench_learn.main([])
    if want("obs"):
        from benchmarks import bench_obs
        # Explicit jax (like the bench_engine/bench_learn default backend
        # lists): "auto" resolves to numpy on CPU, whose run captures no
        # compiled programs — the point of this section.
        obs_args = (["--jobs", "32", "--policies", "12", "--scenarios", "8",
                     "--chunk", "4", "--iters", "2"] if args.quick else [])
        bench_obs.main(obs_args + ["--backend", "jax"])
    if want("roofline"):
        from benchmarks import roofline
        roofline.main([])


if __name__ == "__main__":
    main()
