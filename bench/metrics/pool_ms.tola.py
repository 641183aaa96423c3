"""Pool / realize (core/scheduler.py ``build_plans``, ``_allocate_pool``):
host milliseconds per TOLA run in the program's ``tola.plans`` and
``tola.pool`` spans, the chosen policies' plans and the shared self-owned
pool granted over them, summed over markets and rounds."""

SPANS = ("tola.plans", "tola.pool")


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
