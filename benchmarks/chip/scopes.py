"""Device time of the program's named scopes (``jax.named_scope``) inside
one program of a traced run, from the same ``.xplane.pb`` that
``trace.reduce`` reads.

``trace.reduce`` names an XLA operation by its HLO text alone.  The scope
an operation was traced under is in the metadata of its event: the
``tf_op`` stat holds the op_name XLA carries and the op's type, as
``jit(_decode)/while/body/mla/dot_general:dot_general``.  The profiler's
Python API gives no metadata stats, so this reads the protobuf wire format
of the XSpace (tsl/profiler/protobuf/xplane.proto) itself, only the fields
it needs:

- ``XSpace``: planes = 1;
- ``XPlane``: name = 2, lines = 3, event_metadata = 4 (map of id to
  ``XEventMetadata``), stat_metadata = 5 (map of id to ``XStatMetadata``);
- ``XLine``: name = 2, timestamp_ns = 3, events = 4;
- ``XEvent``: metadata_id = 1, offset_ps = 2, duration_ps = 3;
- ``XEventMetadata``: id = 1, name = 2, stats = 5;
- ``XStat``: metadata_id = 1, str_value = 5, ref_value = 7;
- ``XStatMetadata``: id = 1, name = 2.

An operation belongs to a scope when the scope is one of the ``/``-parts
of its op_name; a loop, whose op_name holds no scope, is not counted, the
operations inside it are.  A program that names no scope gives nothing.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict
from typing import Dict, Iterator, Tuple

import harness

CHIP = "/device:TPU:0"
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of a message: an int for a
    varint or a fixed-width field, bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, val


def _map_values(entry: bytes) -> bytes:
    return next((v for f, v in fields(entry) if f == 2), b"")


def _plane(path: str, name: str):
    with open(path, "rb") as f:
        space = f.read()
    for f_no, plane in fields(space):
        if f_no == 1 and any(f == 2 and v == name.encode()
                             for f, v in fields(plane)):
            return plane
    return None


def _lines(plane: bytes):
    """(event metadata {id: (name, op_name)}, {line name: [(start_ps,
    duration_ps, metadata id)]})."""
    stat_names, metas, lines = {}, {}, {}
    raw_metas = []
    for f_no, val in fields(plane):
        if f_no == 5:
            sm = dict(fields(_map_values(val)))
            stat_names[sm.get(1, 0)] = sm.get(2, b"").decode()
        elif f_no == 4:
            raw_metas.append(_map_values(val))
        elif f_no == 3:
            name, t0, events = "", 0, []
            for g, v in fields(val):
                if g == 2:
                    name = v.decode()
                elif g == 3:
                    t0 = v * 1000
                elif g == 4:
                    ev = dict(fields(v))
                    events.append((t0 + ev.get(2, 0), ev.get(3, 0),
                                   ev.get(1, 0)))
            lines[name] = events
    for raw in raw_metas:
        mid, name, op = 0, "", ""
        for g, v in fields(raw):
            if g == 1:
                mid = v
            elif g == 2:
                name = v.decode(errors="replace")
            elif g == 5:
                st = dict(fields(v))
                if stat_names.get(st.get(1)) == "tf_op":
                    op = (st[5].decode(errors="replace") if 5 in st
                          else stat_names.get(st.get(7), ""))
        metas[mid] = (name, op)
    return metas, lines


@functools.lru_cache(maxsize=4)
def scope_ns(path: str, program: str) -> Dict[str, float]:
    """Device ns of the operations of chip 0 under each scope, summed over
    the runs of ``program`` in the trace."""
    plane = _plane(path, CHIP)
    if plane is None:
        return {}
    metas, lines = _lines(plane)
    runs = []
    for start, dur, mid in lines.get("XLA Modules", ()):
        m = _PROGRAM.match(metas.get(mid, ("", ""))[0])
        if m and m.group(1) == program:
            runs.append((start, start + dur))
    runs.sort()
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for start, dur, mid in sorted(lines.get("XLA Ops", ())):
        while j < len(runs) and runs[j][1] < start:
            j += 1
        if j == len(runs) or start < runs[j][0]:
            continue
        op_name = metas.get(mid, ("", ""))[1].split(":")[0]
        for part in set(op_name.split("/")):
            if part:
                out[part] += dur * 1e-3
    return dict(out)


def of_run(run, cell, program: str) -> Dict[str, float]:
    """Device ns under each scope in ``program``'s runs of the cell's
    traced run (the profile ``run.py`` leaves under ``<checkout>/
    .bench_trace/<cell>`` until every reader has run); none untraced."""
    if run.trace is None:
        return {}
    tr = harness.load_module(".", "trace")
    return scope_ns(tr.find_xplane(cell.root.parents[1] / ".bench_trace" /
                                   cell.name), program)
