"""Kernels (kernels/policy_cost.py, kernels/ref.py): the eval programs'
share of the least time the chip could take for them in a TOLA run, in
percent.

A run calls the chain recurrence once per engine round. Its least time is,
per round, the larger of two bounds worked out from the shapes alone, so
that no implementation can move the yardstick, summed over the rounds:

* bytes: what the recurrence must read and write once, at the f32 the
  configuration states. Round 0 scores a shared plan: each evaluated row's
  task deadlines, cloud workload, cloud parallelism (f32) and self-owned pin
  (one byte) for L tasks, and its arrival. A refinement round scores the
  grid against each market's own residual pool, so the cloud workload,
  parallelism and pin of every row are per scenario (S x rows x L x 9
  bytes) beside the shared deadlines and arrival. Every round reads each
  (bid, scenario) pair's two cumulative views of n_slots + 1 entries and
  writes four f32 outputs per (scenario, row); over the HBM bandwidth;
* operations: per (scenario, row, task) two binary searches of
  ceil(log2(n_slots + 1)) steps and 40 arithmetic operations; over the
  chip's bf16 peak, which is generous to f32 vector work.

At these shapes the bytes bound each round by far.
"""

import math


def eval_bytes(J, groups, L, S, bids, n_slots, per_scenario) -> int:
    rows = groups * J
    if per_scenario:
        plan = rows * L * 4 + S * rows * L * (4 + 4 + 1) + rows * 4
    else:
        plan = rows * L * (4 + 4 + 4 + 1) + rows * 4
    views = bids * S * (n_slots + 1) * 2 * 4
    outputs = S * rows * 4 * 4
    return plan + views + outputs


def eval_ops(J, groups, L, S, n_slots) -> int:
    return S * groups * J * L * (2 * math.ceil(math.log2(n_slots + 1)) + 40)


def least_seconds(shapes, peaks) -> float:
    sh = shapes
    ops = eval_ops(sh["J"], sh["groups"], sh["L"], sh["S"], sh["n_slots"])
    t = 0.0
    for r in range(sh["rounds"]):
        b = eval_bytes(sh["J"], sh["groups"], sh["L"], sh["S"], sh["bids"],
                       sh["n_slots"], per_scenario=r > 0)
        t += max(b / peaks["hbm_bytes_per_s"],
                 ops / peaks["bf16_flops_per_s"])
    return t


PROGRAMS = ("policy_cost_chain", "policy_cost", "_chain_body", "_task_body",
            "_chain_body_ps", "_task_body_ps")


def read(run):
    s = run.trace.program_s(PROGRAMS)
    if run.units == 0 or s <= 0.0 or run.peaks is None:
        return None
    return 100.0 * least_seconds(run.shapes, run.peaks) * run.units / s
