"""Graph lanes: mean device-idle time between consecutive decode programs
(``jit__decode``, as ``Summary.idle_between`` counts it) that no span of
the tick loop covers (``serve.insert``, ``serve.decode``, ``serve.sync``,
``serve.collect``), in ms: the hops between the engine's nodes, thread
wake-ups, waits for the interpreter lock and the program's launch.
Never above ``decode_gap_ms.serve``, which counts the same gaps whole."""

import bisect

PROGRAM = "jit__decode"
TICK = ("serve.insert", "serve.decode", "serve.sync", "serve.collect")


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _uncovered(lo, hi, covered, starts):
    """Length of [lo, hi) that no interval of ``covered`` overlaps."""
    free = hi - lo
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(covered) and covered[i][0] < hi:
        free -= max(0.0, min(covered[i][1], hi) - max(covered[i][0], lo))
        i += 1
    return free


def read(run, cell, peaks):
    tr = run.trace
    runs = tr.programs.get(PROGRAM) if tr else None
    covered = _merged((s, s + d) for s, d, n in tr.host if n in TICK) \
        if tr else []
    if not runs or len(runs) < 2 or not covered:
        return None
    starts = [c[0] for c in covered]
    gaps = [(s, s + d) for s, d in tr.gaps]
    gap_starts = [s for s, _ in gaps]
    hops = []
    for a, b in zip(runs, runs[1:]):
        lo, hi = a.start + a.dur, b.start
        free = 0.0
        i = max(0, bisect.bisect_right(gap_starts, lo) - 1)
        while i < len(gaps) and gaps[i][0] < hi:
            gs, ge = max(gaps[i][0], lo), min(gaps[i][1], hi)
            if ge > gs:
                free += _uncovered(gs, ge, covered, starts)
            i += 1
        hops.append(free)
    return sum(hops) / len(hops) * 1e-6
