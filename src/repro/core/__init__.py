"""The paper's contribution: (near-)optimal deadline + instance allocation
for chain/DAG jobs over self-owned, spot and on-demand instances, plus the
TOLA online-learning layer (paper Sections 4-5)."""

from repro.core.baselines import (
    B_BIDS,
    C1_BETA0,
    C2_BETA,
    benchmark_bid_policies,
    run_even,
    run_greedy,
    selfowned_policies,
    spot_od_policies,
    sweep_policies,
)
from repro.core.dealloc import (
    dealloc,
    expected_spot_work,
    window_sizes,
    window_sizes_batch,
)
from repro.core.market import SpotMarket
from repro.core.policy import f_selfowned, selfowned_allocation, spot_ondemand_split
from repro.core.pool import LazySegmentTree, SelfOwnedPool
from repro.core.scheduler import (
    Policy,
    StreamCosts,
    build_plans_batch,
    evaluate_policy_fullpool,
    job_arrays,
    run_jobs,
)
from repro.core.simulate import simulate_tasks
from repro.core.tola import run_tola_scenarios
from repro.core.transform import chain_of, transform
from repro.core.types import Allocation, ChainJob, DAGJob, Task, chain_from_arrays
from repro.core.workload import generate_chain_jobs, generate_dag_jobs

__all__ = [
    "Allocation", "ChainJob", "DAGJob", "Task", "chain_from_arrays",
    "SpotMarket", "SelfOwnedPool", "Policy", "StreamCosts",
    "dealloc", "window_sizes", "window_sizes_batch", "expected_spot_work",
    "build_plans_batch", "job_arrays", "LazySegmentTree",
    "f_selfowned", "selfowned_allocation", "spot_ondemand_split",
    "simulate_tasks", "run_jobs", "evaluate_policy_fullpool",
    "run_tola_scenarios", "transform", "chain_of",
    "generate_chain_jobs", "generate_dag_jobs",
    "spot_od_policies", "selfowned_policies", "benchmark_bid_policies",
    "run_greedy", "run_even", "sweep_policies", "C1_BETA0", "C2_BETA",
    "B_BIDS",
]
