"""Model step: device time of the prefill programs (``jit__prefill``) per
1,000 prompt tokens they processed in the traced window, in ms.

Each prompt length compiles to its own program, told apart in the trace
by its fingerprint.  Prefill time grows with length, so the fingerprints
ordered by mean time are the lengths the window sent, in ascending order;
where their counts differ, nothing is read."""

PROGRAM = "jit__prefill"


def read(run, cell, peaks):
    runs = run.trace.programs.get(PROGRAM) if run.trace else None
    lengths = sorted(set(run.counters.get("prompt_lengths_sent", ())))
    if not runs or not lengths:
        return None
    by_fp = {}
    for r in runs:
        by_fp.setdefault(r.fingerprint, []).append(r.dur)
    if len(by_fp) != len(lengths):
        return None
    order = sorted(by_fp, key=lambda fp: sum(by_fp[fp]) / len(by_fp[fp]))
    tokens = sum(len(by_fp[fp]) * n for fp, n in zip(order, lengths))
    ns = sum(sum(v) for v in by_fp.values())
    return ns * 1e-6 / (tokens / 1000.0)
