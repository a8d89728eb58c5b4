#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics, taken
on the host clock with the profiler off; with ``--trace 1`` the same run
is traced and the result carries the cell's per-layer metrics, the
device's busy and window seconds and a breakdown of device time and idle
gaps.  Either way the outputs of the timed path are checked against a
plain reference, and each number compared is printed beside its limit, as
the last lines of standard error and under ``checks``, the last key of the
result.  The result is the last line of standard output.

With no TPU, or fewer chips than the cell asks for, or without the
program beside the benchmark, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

CLOCK0 = time.perf_counter()    # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = harness.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program is not at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    harness.enable_compile_cache(jax)
    try:
        devices = harness.require_chips(jax, cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 3

    trace_dir = ROOT / ".bench_trace" / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    ctx = harness.Ctx(jax, cell, args.seed, args.seconds, bool(args.trace),
                      devices, trace_dir=trace_dir)
    path = harness.load_module("paths", cell.path)
    run = path.run(ctx)
    setup_s = ctx.window[0] - CLOCK0
    ctx.log(f"set-up {setup_s:.3f}s; compiles in the window: "
            f"{ctx.counter.count}")

    breakdown = None
    if args.trace:
        tr = harness.load_module(".", "trace")
        peaks = json.loads((HERE / "peaks.json").read_text())
        if run.device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind "
                           f"{run.device['kind']!r} in peaks.json")
        run.trace = tr.reduce(tr.find_xplane(trace_dir))
        run.device["busy_s"] = run.trace.busy_s
        run.device["window_s"] = run.trace.window_s
        metrics = harness.read_per_layer(cell, run,
                                         peaks[run.device["kind"]])
        breakdown = run.trace.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(run.end_to_end, setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end():
            if m["name"] not in values:
                raise KeyError(f"the {cell.path} path gave no "
                               f"{m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(harness.result_line(run, metrics, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
