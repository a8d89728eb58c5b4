#!/usr/bin/env python3
"""Reads the check's numbers of one cell over many seeds in one process,
for setting its limits: the program's readings on every seed, and on the
``--control-seeds`` the control's too (the plain reference computed in
the precision below the configuration's, in the program's place), each
judged by the cell's limits as a run judges the program.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 10

Each seed is a whole run of the cell at its own load (set-up, window,
check), so the readings are those of the timed path at the timed sizes.
One JSON line per seed goes to standard output.  Needs the chips the cell
asks for, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    harness.enable_compile_cache(jax)
    try:
        devices = harness.require_chips(jax, cell.chips)
    except harness.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 3
    path = harness.load_module("paths", cell.path)
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = harness.Ctx(jax, cell, seed, args.seconds, False, devices,
                          control=seed in args.control_seeds)
        run = path.run(ctx)
        out = {"seed": seed, "correct": run.correct, "checks": run.checks,
               "control": run.control, "end_to_end": run.end_to_end,
               "device": run.device, "seconds": time.perf_counter() - t}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
