"""Whole decode step against the chip's peak, in percent: the least time
the step's work could take (``cost.dense_decode_step``: every weight once,
the cache at the live context, and the operations, against ``peaks.json``;
for decode the bytes bound it) over the mean device time of a decode
program in the traced window.  Batch and live context per step are the
window's decode tokens and the cache positions they attended, over the
number of decode programs traced."""

import cost

PROGRAM = "jit__decode"


def read(run, cell, peaks):
    runs = run.trace.programs.get(PROGRAM) if run.trace else None
    n_tok = run.counters.get("decode_tokens", 0)
    if not runs or not n_tok:
        return None
    batch = n_tok / len(runs)
    kv = run.counters["kv_positions"] / len(runs)
    flops, byts = cost.dense_decode_step(cell.config, batch, kv)
    step_ns = sum(r.dur for r in runs) / len(runs)
    return 100.0 * cost.roofline_s(flops, byts, peaks) / (step_ns * 1e-9)
