"""Pool / realize (core/scheduler.py ``_simulate_plan``): host milliseconds
per TOLA run in the program's ``tola.realize`` spans, the realized run of
the chosen policies in each market, summed over markets and rounds."""

SPANS = ("tola.realize",)


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
