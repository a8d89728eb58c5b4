"""Serving engine: mean device-idle time between consecutive decode
programs in the traced window, in ms: the host's share of a token's
time, from the tick loop, refills and result copies."""

PROGRAM = "jit__decode"


def read(run, cell, peaks):
    if run.trace is None:
        return None
    idle = run.trace.idle_between(PROGRAM)
    if not idle:
        return None
    return sum(idle) / len(idle) * 1e-6
