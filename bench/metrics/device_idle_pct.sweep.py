"""Device: percent of the traced window in which no operation ran on the
device (1 - union of the device's op intervals / window), in the sweep
cells."""


def read(run):
    return run.trace.idle_pct()
