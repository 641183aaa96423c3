"""Plan (engine/plan.py): host milliseconds per ``evaluate_grid`` call
spent finding the already cached grid plan, from the program's own spans:
``plan.arrays`` (the job arrays re-derived from the stream),
``plan.fingerprint`` (their content hash, the cache key) and
``plan.lookup`` (the group lookups). The sweep hits the cache on every
call, so this is host time with the device idle."""

SPANS = ("plan.arrays", "plan.fingerprint", "plan.lookup")


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
