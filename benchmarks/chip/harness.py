"""What every cell shares: finding a cell's pieces by name, the device
check, the compile cache, window statistics, per-layer metric readers and
the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds, by those names alone:

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<mix>.json``: the mix, whose ``path`` names the driven path;
- ``paths/<path>.py``: set-up, warm-up, the window and the check;
- ``limits/<cell>.json``: the limit of each number the check compares;
- ``metrics/<metric>.py``: one reader per per-layer metric.

So a new cell, mix, configuration or metric is new files and new entries,
and no edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import threading
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    path: str
    limits: dict
    bench: dict
    root: pathlib.Path = HERE

    def end_to_end(self) -> List[dict]:
        return _for_cell(self.bench["end_to_end"], self.name)

    def per_layer(self) -> List[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if "workloads" not in m
            or cell in m["workloads"]]


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = HERE,
              bench_file: Optional[pathlib.Path] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its pieces found by
    name under ``root``."""
    bench = _json(bench_file or root.parents[1] / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    traffic = _json(root / "traffic" / f"{w['traffic']}.json")
    lim = root / "limits" / f"{name}.json"
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(root / "configs" / f"{w['config']}.json"),
                traffic=traffic, path=traffic["path"],
                limits=_json(lim) if lim.exists() else {}, bench=bench,
                root=root)


def load_module(kind: str, name: str, root: pathlib.Path = HERE):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind} module {path}")
    tag = f"{kind}_{name}".strip("./")
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(ch if ch.isalnum() else "_" for ch in tag), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# device and compile cache
# ---------------------------------------------------------------------------
CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache(jax) -> str:
    """JAX's persistent compile cache at the checkout's fixed path, small
    programs too.  The program, which reads the same variable, is given the
    same directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chips(jax, chips: int) -> list:
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable backend: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU (JAX backend: {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self, jax):
        self.active = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.active and name in self.EVENTS:
            with self._lock:
                self.count += 1


# ---------------------------------------------------------------------------
# statistics over a window
# ---------------------------------------------------------------------------
def percentile(values, q: float) -> float:
    """Nearest-rank percentile over all values (``inf`` allowed): the
    smallest value with at least ``q`` percent of the values at or below
    it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[k - 1]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a path hands back: the window's end-to-end numbers, counters
    for the readers, the check, and the trace if one was taken."""

    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    checks: Dict[str, dict]         # name -> {"value", "limit"}
    attempted: int
    failed: int
    device: dict
    correct: bool
    trace: Any = None               # trace.Summary or None
    control: Optional[dict] = None  # {"checks", "correct"} of the control


class Ctx:
    """What a path is given: the cell, the seed, the window's length, and
    the window's edges, at which the compile counter and (with
    ``trace``) the profiler start and stop."""

    def __init__(self, jax, cell: Cell, seed: int, seconds: float,
                 trace: bool, devices,
                 trace_dir: Optional[pathlib.Path] = None,
                 control: bool = False, hook=None, log=None):
        self.jax = jax
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.trace_dir = trace_dir
        self.control = control          # also judge the control
        self.hook = hook                # tests break the timed path here
        self.counter = CompileCounter(jax)
        self._log = log or (lambda msg: print(msg, file=sys.stderr,
                                              flush=True))
        self._open = False
        self.window = None              # (t0, t1) on the host clock

    def log(self, msg: str) -> None:
        self._log(f"[{self.cell.name}] {msg}")

    def open_window(self) -> float:
        import time
        if self.trace:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(str(self.trace_dir),
                                          profiler_options=opts)
        self.counter.active = True
        self._open = True
        t0 = time.perf_counter()
        self.window = (t0, t0 + self.seconds)
        return t0

    def close_window(self) -> None:
        if not self._open:
            return
        self._open = False
        self.counter.active = False
        if self.trace:
            self.jax.profiler.stop_trace()

    def device_info(self) -> dict:
        return device_info(self.devices)


def read_per_layer(cell: Cell, run: Run, peaks: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        mod = load_module("metrics", m["name"], cell.root)
        v = mod.read(run, cell, peaks)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def judge(values: Dict[str, float], limits: dict) -> Dict[str, dict]:
    """Each number compared beside its limit (NaN where the cell has no
    limit for it, which no value passes)."""
    return {k: {"value": v, "limit": limits.get(k, float("nan"))}
            for k, v in values.items()}


def checks_ok(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def result_line(run: Run, metrics: Dict[str, dict], breakdown=None) -> str:
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics,
           "device": run.device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = run.checks
    return json.dumps(out)
