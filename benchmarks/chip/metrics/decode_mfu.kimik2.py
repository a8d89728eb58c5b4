"""Whole decode step of the latent-attention, expert-share configuration
against the chip's peak, in percent: the least time of the step's work
(``cost_mla.decode_step``: attention and dense weights once, the latent
cache at the live context, the router, the shared expert and the held
experts that took a token, the head; for decode the bytes bound it) over
the mean device time of a decode program in the traced window."""

import cost
import cost_mla

PROGRAM = "jit__decode"


def read(run, cell, peaks):
    runs = run.trace.programs.get(PROGRAM) if run.trace else None
    moe = run.counters.get("moe", {}).get("decode")
    n_tok = run.counters.get("decode_tokens", 0)
    if not runs or not n_tok or not moe or not moe["steps"]:
        return None
    n, steps = len(runs), moe["steps"]
    flops, byts = cost_mla.decode_step(
        cell.config, n_tok / n, run.counters["kv_positions"] / n,
        moe["moe_local_assignments"] / steps,
        moe["moe_experts_touched"] / steps)
    step_ns = sum(r.dur for r in runs) / n
    return 100.0 * cost.roofline_s(flops, byts, peaks) / (step_ns * 1e-9)
