"""Engine (engine/api.py): host milliseconds per TOLA run inside
``evaluate_grid``, the program's own span, summed over both rounds: round 0
against a dedicated pool and the refinement round against the realized
one."""

SPANS = ("evaluate_grid",)


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
