"""Latent attention against its roofline in the decode step, in percent:
the least time of its work (``cost_mla.mla_decode``: every attention
weight once, the latent cache at the live context, against
``peaks.json``) over the device time of the operations under
``jax.named_scope("mla")`` per decode program (``scopes.py``).  Batch and
live context per step are the window's decode tokens and the cache
positions they attended, over the decode programs traced."""

import cost
import cost_mla
import scopes

PROGRAM = "jit__decode"


def read(run, cell, peaks):
    runs = run.trace.programs.get(PROGRAM) if run.trace else None
    n_tok = run.counters.get("decode_tokens", 0)
    if not runs or not n_tok:
        return None
    ns = scopes.of_run(run, cell, PROGRAM).get("mla")
    if not ns:
        return None
    n = len(runs)
    flops, byts = cost_mla.mla_decode(cell.config, n_tok / n,
                                      run.counters["kv_positions"] / n)
    return 100.0 * cost.roofline_s(flops, byts, peaks) / (ns / n * 1e-9)
