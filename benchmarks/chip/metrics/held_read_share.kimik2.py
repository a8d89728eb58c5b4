"""Held layer-experts whose weights a decode step read, in percent: the
engine's decode ``moe_experts_read`` (expert layers and held experts whose
group of routed live tokens was not empty) over the window's decode steps
x expert layers x held experts.  Nothing where the program does not count
what its steps read."""

import cost_mla


def read(run, cell, peaks):
    moe = run.counters.get("moe", {}).get("decode")
    if not moe or not moe.get("steps") or "moe_experts_read" not in moe:
        return None
    c = cell.config
    slots = moe["steps"] * cost_mla.dims(c)["Lm"] * c["n_routed_experts"]
    return 100.0 * moe["moe_experts_read"] / slots
