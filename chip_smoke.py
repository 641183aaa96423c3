"""Smoke test of the policy engine's main path on a TPU, at the scale of
the paper's §6.1 deployment (arXiv 2106.01847): 10,000 type-1 chain jobs
(up to 49 tasks each, ~2,690 time units), the 175-policy self-owned grid
with r = 600 reserved instances, and device-synthesized "fresh" markets.

    python chip_smoke.py               # phases 1-5 on one chip
    python chip_smoke.py --four-chips  # phase 6 only, on a 2x2 v5e host

Phases, each printed as one line with its wall time and XLA compile count:

1. device   — the first JAX device must be a TPU (no CPU fallback).
2. jax      — ``evaluate_grid(backend="jax")`` with the device plan and
              device-synthesized views, twice: the second call compiles
              nothing and is served from the plan cache.
3. pallas   — the same call through the Pallas chain kernel
              (``interpret=False``); the program must hold a Mosaic
              ``tpu_custom_call``; compared with phase 2.
4. oracle   — the float64 numpy backend on the first scenario: the same
              best policy, unit costs within ``P99_TOL`` / ``CELL_TOL``
              and per-policy alpha within ``ALPHA_TOL``.
5. tola     — ``run_tola_scenarios(backend="jax", pool_iters=1)`` (round 0
              plus one per-scenario-availability refinement round), then
              the Hedge replay of its cost tensor on device (jax scan and
              Pallas kernel) against the float64 event loop.
6. four     — a 2x2 ``GridMesh``: sharded ``evaluate_grid``, one sharded
              refinement round and the sharded learn fold, each against
              the unsharded single-device call; the fold holds exactly one
              all-reduce.

Everything runs in this one process; no child process touches the chip.
The last line of standard output is the JSON result; any failed phase
exits nonzero before it is printed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PLATFORM = "tpu"
JOBS = 10_000            # §6.1: ~10,000 jobs per stream
JOB_TYPE = 1             # chains of up to 49 tasks
R_TOTAL = 600            # self-owned instances, from §6.1's r grid
SCENARIO_SEED = 1000     # benchmarks/common.py's market seed offset
S_JAX = 4                # scenarios of phase 2 (device time grows with S)
S_PALLAS = 1             # phase 3: one scenario, against phase 2's
S_TOLA = 2               # phase 5: the learner replay is host float64
S_FOUR = 2               # phase 6: one scenario per "data" shard; the
                         # unsharded reference runs on a single chip

# Unit-cost bounds between two engines, of which at least one is f32. The
# 1e-5 contract of DESIGN §6 was set at ~200-unit horizons; here absolute
# time reaches ~2,690 units, where one f32 ulp of the chain clock and of
# the cumulative availability integral is ~2.4e-4 time units (ROADMAP R3).
# On one v5e chip, jax against the float64 oracle over 1.75M cells: p99
# 2.2e-5, 6.7% of cells above 1e-5, max 0.100. The few large cells are
# knife-edge flips: an ulp moves one task's spot/on-demand turn, and the
# chain's early starts carry the shift into every later task of the job,
# so one job's unit cost moves by a share of (p_od - spot price), p_od = 1.
# The bulk of the distribution is bounded tightly; the flips loosely.
P99_TOL = 1e-4
CELL_TOL = 0.25
# The stream-level alpha per policy averages those flips out (chip: 1.8e-4
# against the oracle, 2.7e-6 between jax and Pallas); it decides the best
# policy, so it gets the tight bound.
ALPHA_TOL = 1e-3
# Device Hedge replay (f32 log-weights over 10k updates) against the
# float64 event loop: sampled traces may differ only where a uniform draw
# lands within float error of a CDF step.
LEARN_TRACE_AGREE = 0.999
LEARN_WEIGHT_TOL = 1e-3


def need(ok, what) -> None:
    """Fail the run when a check does not hold (unlike ``assert``, also
    under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def phase(name: str):
    """Time one phase and count its XLA compiles; print its line."""
    from repro.obs.compiled import CompileWatch

    info: dict = {}
    watch = CompileWatch()
    t0 = time.perf_counter()
    with watch:
        yield info
    fields = " ".join(f"{k}={v}" for k, v in info.items())
    print(f"[{name}] wall_s={time.perf_counter() - t0:.3f} "
          f"compiles={watch.compiles} {fields}", flush=True)


def device_phase(n_chips: int) -> dict:
    import jax

    with phase("device") as info:
        devs = jax.devices()
        d0 = devs[0]
        info.update(platform=d0.platform, kind=repr(d0.device_kind),
                    count=len(devs))
        if d0.platform != PLATFORM:
            raise SystemExit(f"chip_smoke: needs a {PLATFORM} device, JAX "
                             f"found {d0.platform!r}")
        if len(devs) < n_chips:
            raise SystemExit(f"chip_smoke: needs {n_chips} devices, JAX "
                             f"found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class Workload:
    """The §6.1 stream, the self-owned grid and its market family."""

    def __init__(self):
        from repro.core import generate_chain_jobs, selfowned_policies

        self.jobs = generate_chain_jobs(JOBS, JOB_TYPE, seed=0)
        self.policies = selfowned_policies()
        self.horizon = max(j.deadline for j in self.jobs) + 1.0

    def spec(self, n_scenarios: int):
        from repro.engine import ScenarioSpec

        return ScenarioSpec("fresh", self.horizon, n_scenarios,
                            seed=SCENARIO_SEED)

    def evaluate(self, n_scenarios: int, **kw):
        from repro.engine import evaluate_grid

        return evaluate_grid(self.jobs, self.policies,
                             self.spec(n_scenarios), R_TOTAL, **kw)


def jax_phase(w: Workload):
    from repro.engine import cache
    from repro.obs.compiled import CompileWatch

    with phase("jax") as info:
        t0 = time.perf_counter()
        cold = w.evaluate(S_JAX, backend="jax")
        t1 = time.perf_counter()
        warm_watch = CompileWatch()
        with warm_watch:
            warm = w.evaluate(S_JAX, backend="jax")
        t2 = time.perf_counter()
        groups = len(cache.PLAN_CACHE)
        info.update(S=S_JAX, jobs=len(w.jobs), policies=len(w.policies),
                    n_slots=w.spec(1).n_slots, groups=groups,
                    cold_s=f"{t1 - t0:.3f}", warm_s=f"{t2 - t1:.3f}",
                    warm_compiles=warm_watch.compiles,
                    warm_plan_cached=warm.timings["plan_cached"])
        need(cold.unit_cost.shape == (S_JAX, len(w.jobs), len(w.policies)),
             cold.unit_cost.shape)
        need(np.isfinite(cold.unit_cost).all(), "finite unit costs")
        need(np.array_equal(cold.unit_cost, warm.unit_cost),
             "warm call reproduces the cold call")
        need(warm_watch.compiles == 0, f"warm compiles {warm_watch.compiles}")
        need(warm.timings["plan_cached"] == groups > 0,
             f"plan-cache hits {warm.timings['plan_cached']} of {groups}")
    return cold


def compare(a, b, s: int = 0) -> dict:
    """Unit-cost and per-policy alpha differences of scenario ``s``."""
    d = np.abs(a.unit_cost[s] - b.unit_cost[s])
    a_a, a_b = a.avg_unit_cost()[s], b.avg_unit_cost()[s]
    return {"max_abs_diff": float(d.max()),
            "p99_diff": float(np.quantile(d, 0.99)),
            "cells_over_1e-5": int((d > 1e-5).sum()), "cells": d.size,
            "alpha_diff": float(np.abs(a_a - a_b).max()),
            "best": (int(a_a.argmin()), int(a_b.argmin()))}


def check(diff: dict) -> None:
    need(diff["best"][0] == diff["best"][1], f"best policy {diff}")
    need(diff["p99_diff"] <= P99_TOL, f"p99 tolerance {diff}")
    need(diff["max_abs_diff"] <= CELL_TOL, f"cell tolerance {diff}")
    need(diff["alpha_diff"] <= ALPHA_TOL, f"alpha tolerance {diff}")


def pallas_phase(w: Workload, ref):
    from repro.obs import capture

    with phase("pallas") as info:
        with capture() as reg:
            res = w.evaluate(S_PALLAS, backend="pallas")
        kernel = reg.entries["engine.eval.pallas_chain"]
        need("error" not in kernel, kernel)
        diff = compare(res, ref)
        info.update(S=S_PALLAS, tpu_custom_calls=kernel["tpu_custom_calls"],
                    vs_jax=diff)
        if PLATFORM == "tpu":
            need(kernel["tpu_custom_calls"] >= 1, f"Mosaic kernel {kernel}")
        need(np.isfinite(res.unit_cost).all(), "finite unit costs")
        check(diff)


def oracle_phase(w: Workload, ref):
    with phase("oracle") as info:
        orc = w.evaluate(1, backend="numpy")
        diff = compare(orc, ref)
        info.update(S=1, vs_f64=diff, p99_tol=P99_TOL, cell_tol=CELL_TOL,
                    alpha_tol=ALPHA_TOL,
                    alpha_best=float(orc.avg_unit_cost()[0].min()))
        check(diff)


def tola_phase(w: Workload):
    from repro.core import run_tola_scenarios
    from repro.learn import replay

    with phase("tola") as info:
        markets = w.spec(S_TOLA).materialize()
        res = run_tola_scenarios(w.jobs, w.policies, markets, R_TOTAL,
                                 seed=0, pool_iters=1, backend="jax",
                                 learner="hedge")
        alpha = [r.average_unit_cost() for r in res]
        top = [int(r.weights.argmax()) for r in res]
        info.update(S=S_TOLA, realized_alpha=[f"{a:.6f}" for a in alpha],
                    best_fixed=[f"{r.best_fixed_unit_cost:.6f}" for r in res],
                    top_policy=top)
        need(len(res) == S_TOLA and np.isfinite(alpha).all(), alpha)
        # The learner replay on device over the refined cost tensor.
        C = np.stack([r.cost_matrix for r in res])
        arrivals = np.array([j.arrival for j in w.jobs])
        d = max(j.deadline - j.arrival for j in w.jobs)
        Z = np.array([j.total_work for j in w.jobs])
        ref = replay(C, arrivals, d, workload=Z, seed=7, backend="numpy")
        for backend in ("jax", "pallas"):
            got = replay(C, arrivals, d, workload=Z, seed=7, backend=backend)
            agree = float((got.chosen == ref.chosen).mean())
            wdiff = float(np.abs(got.weights - ref.weights).max())
            info[f"{backend}_trace_agree"] = agree
            info[f"{backend}_weight_diff"] = wdiff
            need(agree >= LEARN_TRACE_AGREE, f"{backend} traces {agree}")
            need(wdiff <= LEARN_WEIGHT_TOL, f"{backend} weights {wdiff}")


def four_chip_phase(w: Workload):
    from repro.core import run_tola_scenarios
    from repro.engine import GridMesh
    from repro.learn import replay_stream
    from repro.obs import capture

    with phase("four") as info:
        mesh = GridMesh.create(2, model_devices=2)
        info.update(n_shards=mesh.n_shards, data=mesh.data_shards,
                    model=mesh.model_shards, S=S_FOUR)
        need((mesh.n_shards, mesh.data_shards, mesh.model_shards)
             == (4, 2, 2), info)
        un = w.evaluate(S_FOUR, backend="jax")
        sh = w.evaluate(S_FOUR, backend="jax", mesh=mesh)
        info["eval_bitwise"] = bool(np.array_equal(un.unit_cost,
                                                   sh.unit_cost))
        markets = w.spec(S_FOUR).materialize()
        kw = dict(seed=0, pool_iters=1, backend="jax")
        t_un = run_tola_scenarios(w.jobs, w.policies, markets, R_TOTAL, **kw)
        t_sh = run_tola_scenarios(w.jobs, w.policies, markets, R_TOTAL,
                                  mesh=mesh, **kw)
        info["refine_bitwise"] = bool(all(
            np.array_equal(a.cost_matrix, b.cost_matrix)
            and np.array_equal(a.chosen, b.chosen)
            for a, b in zip(t_un, t_sh)))
        lkw = dict(learners=["hedge"], seed=3, scenario_chunk=S_FOUR,
                   backend="jax", engine_backend="jax")
        host = replay_stream(w.jobs, w.policies, w.spec(S_FOUR), R_TOTAL,
                             **lkw)
        with capture() as reg:
            folded = replay_stream(w.jobs, w.policies, w.spec(S_FOUR),
                                   R_TOTAL, mesh=mesh, **lkw)
        fold = reg.entries["learn.fold:sharded"]
        need("error" not in fold, fold)
        allreduce = fold["collective_counts"]["all-reduce"]
        fold_diff = float(np.abs(host.regret_per_job()
                                 - folded.regret_per_job()).max())
        info.update(fold_all_reduce=allreduce,
                    fold_collectives=fold["collective_counts"]["total"],
                    fold_regret_diff=fold_diff)
        need(info["eval_bitwise"] and info["refine_bitwise"], info)
        need(allreduce == 1 and fold["collective_counts"]["total"] == 1,
             fold["collective_counts"])
        # device f32 fold against the host fold of the same traces
        need(fold_diff < 1e-4, f"fold regret {fold_diff}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 2x2 GridMesh phase (needs 4 chips)")
    args = p.parse_args(argv)

    from repro.engine import setup_persistent_cache

    setup_persistent_cache()
    device = device_phase(4 if args.four_chips else 1)
    with phase("setup") as info:
        w = Workload()
        info.update(jobs=len(w.jobs), policies=len(w.policies),
                    horizon=f"{w.horizon:.3f}",
                    L_max=max(len(j.tasks) for j in w.jobs))
    if args.four_chips:
        four_chip_phase(w)
    else:
        ref = jax_phase(w)
        pallas_phase(w, ref)
        oracle_phase(w, ref)
        tola_phase(w)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
