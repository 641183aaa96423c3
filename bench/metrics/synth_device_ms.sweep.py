"""Scenario layer (engine/scenarios.py): device milliseconds per
``evaluate_grid`` call of the market synthesis program (``gen``) and the
per-bid view program (``views``), from the profiler trace."""

PROGRAMS = ("gen", "views")


def read(run):
    s = run.trace.program_s(PROGRAMS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
