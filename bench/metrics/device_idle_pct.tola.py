"""Device: percent of the traced window in which no operation ran on the
device (1 - union of the device's op intervals / window), in the TOLA
cells, where the learner, the shared pool and the realized run are host
work."""


def read(run):
    return run.trace.idle_pct()
