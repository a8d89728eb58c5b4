"""The serving engine's own spans (``serve.*``) of a traced run, with their
stats, which ``trace.reduce`` drops.

The engine writes them with ``jax.profiler.TraceAnnotation`` into the
profiler's trace: each is an event on the line of the host thread that did
the work, in the ``/host:CPU`` plane, named ``serve.<node>`` and carrying
its stats (``req``, ``step``, ``queued_us``, ...) as event stats.  Times are
nanoseconds on the clock of the device planes.  A program that writes no
such span gives an empty list.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import harness

PREFIX = "serve."


@dataclasses.dataclass(frozen=True)
class Span:
    start: float            # ns, as the device planes count them
    dur: float              # ns
    name: str
    stats: Dict[str, float]


@functools.lru_cache(maxsize=1)
def read(path: str) -> Tuple[Span, ...]:
    """Every ``serve.*`` span in the profile at ``path``, in start order."""
    import jax
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend(Span(e.start_ns, e.duration_ns, e.name,
                            dict(e.stats))
                       for e in line.events if e.name.startswith(PREFIX))
    return tuple(sorted(out, key=lambda s: s.start))


def of_run(run, cell) -> Tuple[Span, ...]:
    """The spans of the cell's traced run, from the profile that ``run.py``
    leaves under ``<checkout>/.bench_trace/<cell>`` until every reader has
    run; none for an untraced run."""
    if run.trace is None:
        return ()
    tr = harness.load_module(".", "trace")
    return read(tr.find_xplane(cell.root.parents[1] / ".bench_trace" /
                               cell.name))


def stat_ms(spans, name: str, stat: str):
    """``stat`` (in us) of every ``name`` span, in ms."""
    return [s.stats[stat] * 1e-3 for s in spans
            if s.name == name and stat in s.stats]
