"""Model step: mean device time of one batched decode program
(``jit__decode``, the engine's ``_decode``) in the traced window, in ms."""

PROGRAM = "jit__decode"


def read(run, cell, peaks):
    runs = run.trace.programs.get(PROGRAM) if run.trace else None
    if not runs:
        return None
    return sum(r.dur for r in runs) / len(runs) * 1e-6
