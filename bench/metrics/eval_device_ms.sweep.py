"""Eval layer (engine/backend_jax.py, engine/backend_pallas.py): device
milliseconds per ``evaluate_grid`` call of every cost program, the Pallas
chain / task kernels and the jax chain / task programs alike, whichever the
engine's ``auto`` backend resolved to. The small programs that stack the
plan rows for a kernel are not counted here (they show in ``breakdown``)."""

PROGRAMS = ("policy_cost_chain", "policy_cost", "_chain_body", "_task_body",
            "_chain_body_ps", "_task_body_ps")


def read(run):
    s = run.trace.program_s(PROGRAMS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
