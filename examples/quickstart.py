"""Quickstart: the paper's core pipeline in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

1. Reproduces the paper's worked example (Fig. 3/4): optimal deadline
   allocation puts 22/6 units of work on spot instances.
2. Generates a Section-6.1 job stream, prices it under the proposed policy
   (Algorithm 2) vs the Greedy/Even baselines, and runs TOLA online
   learning over the policy grid.
"""

import numpy as np

from repro.core import (
    B_BIDS,
    SpotMarket,
    chain_from_arrays,
    expected_spot_work,
    generate_chain_jobs,
    run_greedy,
    run_jobs,
    run_tola_scenarios,
    spot_od_policies,
    window_sizes,
)

# --- 1. the paper's Fig. 3/4 example ---------------------------------------
job = chain_from_arrays(0.0, 4.0, z=[1.5, 0.5, 2.5, 0.5], delta=[2, 1, 3, 1])
sizes = window_sizes(job, x=0.5)   # Dealloc(beta = 0.5)
zo = expected_spot_work(job.z_array(), job.delta_array(), sizes, 0.5)
print(f"optimal windows: {np.round(sizes, 4)}  "
      f"spot workload: {zo.sum():.4f} (= 22/6, paper Fig. 4)")

# --- 2. a job stream under the proposed policy vs baselines -----------------
jobs = generate_chain_jobs(300, job_type=1, seed=7)
market = SpotMarket(max(j.deadline for j in jobs) + 1, seed=11)

best = min(run_jobs(jobs, p, market).average_unit_cost()
           for p in spot_od_policies())
greedy = min(run_greedy(jobs, b, market).average_unit_cost() for b in B_BIDS)
even = min(run_jobs(jobs, p, market, windows="even",
                    early_start=False).average_unit_cost()
           for p in spot_od_policies())
print(f"alpha proposed {best:.4f} | greedy {greedy:.4f} | even {even:.4f}")
print(f"cost improvement: {1 - best / greedy:.2%} vs greedy, "
      f"{1 - best / even:.2%} vs even")

# --- 3. online learning (TOLA) over the policy grid -------------------------
res = run_tola_scenarios(jobs, spot_od_policies(), [market], seed=0)[0]
print(f"TOLA realized alpha {res.average_unit_cost():.4f}, "
      f"best fixed {res.best_fixed_unit_cost:.4f}, "
      f"top policy weight {res.weights.max():.3f}")
