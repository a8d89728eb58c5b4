"""Device: share of the traced window in which no program ran on the
chip, in percent (1 - union of program intervals / window)."""


def read(run, cell, peaks):
    return run.trace.idle_pct if run.trace is not None else None
