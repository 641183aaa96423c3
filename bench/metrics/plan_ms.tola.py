"""Plan (engine/plan.py): host milliseconds per TOLA run in the program's
``plan`` and ``pool`` spans, both rounds: the windows and policy-(12)
counts of the grid, and in the refinement round the availability queries
against the realized pool."""

SPANS = ("plan", "pool")


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
