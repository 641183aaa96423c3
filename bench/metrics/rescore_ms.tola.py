"""Engine (core/tola.py over engine/api.py): host milliseconds per TOLA run
in the program's ``tola.rescore`` spans, the refinement rounds' engine
calls: the grid re-planned on the availability queries against each
market's realized pool, the per-scenario plan stacks and the chain kernel
on them."""

SPANS = ("tola.rescore",)


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
