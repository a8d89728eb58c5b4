"""Expert layers against their roofline in the decode step, in percent:
the least time of their work (``cost_mla.moe_decode``: the router and the
shared expert for every token, and the held experts that took a token,
from the engine's ``moe_experts_touched`` and ``moe_local_assignments``
over the window's decode steps) over the device time of the operations
under ``jax.named_scope("moe")`` per decode program (``scopes.py``)."""

import cost
import cost_mla
import scopes

PROGRAM = "jit__decode"


def read(run, cell, peaks):
    runs = run.trace.programs.get(PROGRAM) if run.trace else None
    moe = run.counters.get("moe", {}).get("decode")
    n_tok = run.counters.get("decode_tokens", 0)
    if not runs or not n_tok or not moe or not moe["steps"]:
        return None
    ns = scopes.of_run(run, cell, PROGRAM).get("moe")
    if not ns:
        return None
    n, steps = len(runs), moe["steps"]
    flops, byts = cost_mla.moe_decode(
        cell.config, n_tok / n, moe["moe_local_assignments"] / steps,
        moe["moe_experts_touched"] / steps)
    return 100.0 * cost.roofline_s(flops, byts, peaks) / (ns / n * 1e-9)
