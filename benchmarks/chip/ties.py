#!/usr/bin/env python3
"""Where an expert-share cell's served tokens part from its reference, and
whether the router's near-ties account for it.

For one seed, the program decodes the mix's requests with the longest
answers greedily, one at a time, through its own prefill and decode steps
(``runtime/steps.py``, the functions the engine wraps) at the cell's cache
length, and keeps which held experts every position picked in every
expert layer.  Then the reference computes the same sequences in float32,
with its own routing: which held experts it picks, and for each position
the least change of a held expert's biased score that would move it
across the k-th largest (its "tie").

    python3 benchmarks/chip/ties.py --workload serve.kimik2-s9e8.longout \\
        --seed 1234567891 --requests 2

One JSON line per request goes to standard output: the per-token logit
gaps (as the cell's check reads them) at positions where the program's
held experts match the reference's in every expert layer and where they do
not, the reference's ties at the positions that differ against all ties,
and the widest gap with what the routing did there.  Needs the chips the
cell asks for, like ``run.py``.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def _serve(cfg, params, prompt, max_new: int, cache_len: int):
    """(greedy tokens, the held experts each position picked: (expert
    layers, len(prompt) + max_new - 1, held) bool)."""
    import jax
    import jax.numpy as jnp
    from repro.core.plan import single_device_plan
    from repro.runtime.steps import make_decode_step, make_prefill_step
    plan = single_device_plan()
    prefill = jax.jit(make_prefill_step(cfg, plan, cache_len, stats=True))
    decode = jax.jit(make_decode_step(cfg, plan, cache_len, stats=True),
                     donate_argnums=1)
    logits, caches, st = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    toks, hits = [tok], [st["moe_hits"]]
    for i in range(max_new - 1):
        pos = jnp.asarray([len(prompt) + i], jnp.int32)
        tok, _, caches, st = decode(params, caches, {"token": tok, "pos": pos})
        toks.append(tok)
        hits.append(st["moe_hits"])
    toks, hits = jax.device_get((toks, hits))
    return (np.concatenate([t[:, 0] for t in toks]),
            np.concatenate(hits, 1).astype(bool))


def examine(cell, seed: int, n_requests: int, seconds: float, log) -> list:
    """One dict per request, as the module's docstring says."""
    import jax
    import jax.numpy as jnp
    import traffic as T
    import weights as W
    from repro.models.lm import LM
    c, mix = cell.config, cell.traffic
    ref = harness.load_module("refs", c["program"]["reference"], cell.root)
    cfg = harness.load_module("paths", cell.path,
                              cell.root).program_config(c)
    layout = ref.layout(c)
    W.check_layout(layout, LM(cfg).param_defs())
    dtype = jnp.dtype(c["program"]["dtype"])
    reqs = T.serve_requests(mix, seed, seconds, c["vocab_size"])
    reqs = sorted(reqs, key=lambda r: (-r["max_new"], -len(r["prompt"])))
    reqs = reqs[:n_requests]
    params = W.init_on_device(seed, layout, dtype)
    served = []
    for r in reqs:
        served.append(_serve(cfg, params, r["prompt"], r["max_new"],
                             mix["cache_len"]))
        log(f"served {r['max_new']} tokens after a prompt of "
            f"{len(r['prompt'])}")
    del params
    gc.collect()
    seqs = [np.concatenate([r["prompt"], t[:-1]]).astype(np.int32)
            for r, (t, _) in zip(reqs, served)]
    rows = [np.arange(len(r["prompt"]) - 1, len(s))
            for r, s in zip(reqs, seqs)]
    logits, routes = ref.logits_at(seed, c, seqs, rows, dtype, "f32",
                                   routes=True)
    out = []
    for r, (toks, hits), lg, (picked, tie), at in zip(reqs, served, logits,
                                                      routes, rows):
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        flips = hits != picked                      # (layers, len, held)
        flip_at = flips.any(-1)                     # (layers, len)
        row_flip = flip_at[:, at].any(0)            # per served token
        worst = int(gap.argmax())
        p = int(at[worst])
        before = np.nonzero(flip_at[:, :p + 1].any(0))[0]
        out.append({
            "seed": seed, "prompt": len(r["prompt"]), "tokens": len(toks),
            "gap_mean": float(gap.mean()), "gap_max": float(gap.max()),
            "gap_above_0": int((gap > 0).sum()),
            "rows_with_flip": int(row_flip.sum()),
            "gap_mean_with_flip": float(gap[row_flip].mean())
            if row_flip.any() else None,
            "gap_max_without_flip": float(gap[~row_flip].max())
            if (~row_flip).any() else None,
            "gap_mean_without_flip": float(gap[~row_flip].mean())
            if (~row_flip).any() else None,
            "flips_per_layer": flip_at.sum(1).tolist(),
            "positions": int(flip_at.shape[1]),
            "tie_median": float(np.median(tie)),
            "tie_at_flips_max": float(tie[flip_at].max())
            if flip_at.any() else None,
            "tie_at_flips_median": float(np.median(tie[flip_at]))
            if flip_at.any() else None,
            "worst": {"position": p, "gap": float(gap[worst]),
                      "flip_layers": np.nonzero(flip_at[:, p])[0].tolist(),
                      "tie_min": float(tie[:, p].min()),
                      "tie_min_share_below": float(
                          (tie < tie[:, p].min()).mean()),
                      "last_flip_before": int(before[-1])
                      if len(before) else None}})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    harness.enable_compile_cache(jax)
    try:
        harness.require_chips(jax, cell.chips)
    except harness.NoChip as e:
        print(f"ties.py: {e}", file=sys.stderr)
        return 3
    for line in examine(cell, args.seed, args.requests, args.seconds,
                        lambda m: print(m, file=sys.stderr, flush=True)):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
