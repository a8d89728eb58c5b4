#!/usr/bin/env python3
"""Sweeps the arrival rate of an open-loop serving cell, to find the
highest rate the system sustains (the knee) before a rate is written into
the cell's mix.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \\
        --seconds 50 --seed 7 [--write]

One JSON line per rate: the tails, the requests sent and first served in
the window, and how late the generator ran.  A rate is sustained while the
queue does not grow through the window: the median time to first token of
the window's last third of requests is at most twice that of its first
third, plus 100 ms.  The knee is the highest rate below the first that is
not sustained; the last line gives it and four fifths of it, which
``--write`` writes into the mix as its ``rate_per_s``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--write", action="store_true",
                    help="write 4/5 of the knee into the mix file")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    harness.enable_compile_cache(jax)
    try:
        devices = harness.require_chips(jax, cell.chips)
    except harness.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 3
    path = harness.load_module("paths", cell.path)
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        ctx = harness.Ctx(jax, cell, args.seed, args.seconds, False, devices)
        run = path.run(ctx)
        st = run.counters
        third = max(1, len(st["ttft_ms"]) // 3)
        first = harness.percentile(st["ttft_ms"][:third], 50)
        last = harness.percentile(st["ttft_ms"][-third:], 50)
        sustained = bool(last <= 2 * first + 100.0)
        print(json.dumps({
            "rate_per_s": rate, "sent": st["sent"],
            "first_tokens_in_window": len(st["prompt_lengths"]),
            "ttft_p50_ms": harness.percentile(st["ttft_ms"], 50),
            "ttft_p95_ms": run.end_to_end["ttft_p95_ms"],
            "ttft_p50_first_third_ms": first,
            "ttft_p50_last_third_ms": last,
            "itl_p95_ms": run.end_to_end["itl_p95_ms"],
            "tokens_per_s": run.end_to_end["tokens_per_s"],
            "send_lag_p99_ms": harness.percentile(st["send_lag_s"], 99)
            * 1e3, "shed": st["shed"], "sustained": sustained,
            "correct": bool(run.correct)}), flush=True)
        if not sustained:
            break
        knee = rate
    if knee is None:
        print(json.dumps({"knee_per_s": None}), flush=True)
        return 1
    rate = round(0.8 * knee, 3)
    print(json.dumps({"knee_per_s": knee, "rate_per_s": rate}), flush=True)
    if args.write:
        mix = next(w["traffic"] for w in cell.bench["workloads"]
                   if w["name"] == cell.name)
        f = HERE / "traffic" / f"{mix}.json"
        text = re.sub(r'"rate_per_s": [0-9.eE+-]+', f'"rate_per_s": {rate}',
                      f.read_text(), count=1)
        f.write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
