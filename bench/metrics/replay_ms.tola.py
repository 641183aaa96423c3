"""Learn (learn/replay.py): host milliseconds per TOLA run in the program's
``replay`` span, the learner's draws and weight updates over a cost
matrix, summed over markets and rounds."""

SPANS = ("replay",)


def read(run):
    s = run.span_s(SPANS)
    if run.units == 0 or s <= 0.0:
        return None
    return 1e3 * s / run.units
