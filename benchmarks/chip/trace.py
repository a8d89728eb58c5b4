"""Reduces a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, whose
``XLA Modules`` line holds one event per program run, named
``<program>(<fingerprint>)`` (``jit__decode(1393...)``): the program is the
jitted function's name and the fingerprint tells its compiled shapes
apart.  The ``XLA Ops`` line holds the operations inside them, named by
their HLO text; a Pallas kernel is a ``tpu_custom_call`` among them.
Host threads are lines of ``/host:CPU``.  The ``Task Environment`` plane
gives the traced window's start and stop.  Event times are nanoseconds
from the window's start.

Busy time is the union of the program intervals on a chip, averaged over
the chips; idle is the rest of the window.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")
_OP = re.compile(r"^%?([^ =]+)")
_KERNEL = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel
_LONG_HOST_NS = 1e9     # a host event this long spans the window: no cause


@dataclasses.dataclass
class Run_:
    start: float
    dur: float
    fingerprint: str


@dataclasses.dataclass
class Summary:
    window_ns: float
    devices: int
    busy_ns: float                          # mean over the chips
    programs: Dict[str, List[Run_]]         # chip 0's program runs, in order
    ops: Dict[str, float]                   # "<program>/<op>" -> total ns
    kernels: Dict[str, List[float]]         # Pallas calls: [count, ns]
    gaps: List[Tuple[float, float]]         # chip 0's idle gaps (start, ns)
    host: List[Tuple[float, float, str]]    # host events (start, ns, name)

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_ns / self.window_ns)

    def program_ns(self, name: str) -> float:
        return sum(r.dur for r in self.programs.get(name, ()))

    def idle_between(self, name: str) -> List[float]:
        """Device-idle ns between consecutive runs of ``name``: the idle
        gaps that lie between the end of one run and the start of the
        next."""
        runs = self.programs.get(name, [])
        starts = [g[0] for g in self.gaps]
        out = []
        for a, b in zip(runs, runs[1:]):
            lo, hi = a.start + a.dur, b.start
            i = bisect.bisect_left(starts, lo)
            idle = 0.0
            while i < len(self.gaps) and self.gaps[i][0] < hi:
                gs, gd = self.gaps[i]
                idle += max(0.0, min(gs + gd, hi) - gs)
                i += 1
            out.append(idle)
        return out

    def breakdown(self, n: int = 10) -> dict:
        """The ``n`` operations that took most device time and the ``n``
        longest idle gaps, each named by the host event that overlapped it
        most."""
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[self._cause(s, d), d * 1e-9]
                              for s, d in gaps]}

    def _cause(self, start: float, dur: float) -> str:
        best, name = 0.0, "no host event"
        for hs, hd, hn in self.host:
            if hd >= _LONG_HOST_NS:
                continue
            ov = min(hs + hd, start + dur) - max(hs, start)
            if ov > best:
                best, name = ov, hn
        return name[:80]


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, window_ns) -> List[Tuple[float, float]]:
    out, end = [], 0.0
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, s - end))
        end = max(end, e)
    if window_ns > end:
        out.append((end, window_ns - end))
    return out


def find_xplane(log_dir) -> str:
    found = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce(path) -> Summary:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    window = None
    chips: List[List[Tuple[float, float, str, str]]] = []
    op_lines: List[list] = []
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_stop_time" in st:
                window = float(st["profile_stop_time"]
                               - st["profile_start_time"])
        elif plane.name.startswith("/device:TPU:"):
            runs, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        m = _PROGRAM.match(e.name)
                        name, fp = (m.group(1), m.group(2)) if m \
                            else (e.name, "")
                        runs.append((e.start_ns, e.duration_ns, name, fp))
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns, e.name)
                           for e in line.events]
            chips.append(sorted(runs))
            op_lines.append(ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.duration_ns, e.name)
                            for e in line.events)
    if window is None:
        raise ValueError(f"{path}: no Task Environment plane")
    if not chips or not any(chips):
        raise ValueError(f"{path}: no program ran on a TPU")
    busy = sum(_union([(s, s + d) for s, d, _, _ in c]) for c in chips) \
        / len(chips)
    programs: Dict[str, List[Run_]] = defaultdict(list)
    for s, d, name, fp in chips[0]:
        programs[name].append(Run_(s, d, fp))
    ops: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    mods = chips[0]
    i = 0
    for s, d, text in sorted(op_lines[0]):
        while i < len(mods) and mods[i][0] + mods[i][1] < s:
            i += 1
        prog = mods[i][2] if i < len(mods) and mods[i][0] <= s else "?"
        m = _OP.match(text)
        key = f"{prog}/{m.group(1) if m else text[:40]}"
        ops[key] += d
        if _KERNEL in text:
            kernels[key][0] += 1
            kernels[key][1] += d
    return Summary(window_ns=window, devices=len(chips), busy_ns=busy,
                   programs=dict(programs), ops=dict(ops),
                   kernels=dict(kernels),
                   gaps=_gaps([(s, s + d) for s, d, _, _ in chips[0]],
                              window),
                   host=host)
