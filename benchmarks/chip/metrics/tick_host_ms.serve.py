"""Serving engine: host work on the tick's path per decode step, in ms:
the summed durations of the window's ``serve.insert``, ``serve.decode``
and ``serve.collect`` spans over the number of ``serve.decode`` spans."""

TICK = ("serve.insert", "serve.decode", "serve.collect")


def read(run, cell, peaks):
    if run.trace is None:
        return None
    host = [(d, n) for _, d, n in run.trace.host if n in TICK]
    steps = sum(1 for _, n in host if n == "serve.decode")
    if not steps:
        return None
    return sum(d for d, _ in host) / steps * 1e-6
