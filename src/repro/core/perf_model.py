"""Performance model — paper Sec. 13, extended with the TPU roofline.

The paper's algebra:
  * farm:     T(m tasks, nw workers) ~= T_seq / nw, bounded by emitter /
              collector service times and Amdahl's law;
  * pipeline: service time T_S = max_i T_Si; speedup = sum T_Si / max T_Si.

We reuse exactly that algebra to pick pipeline microbatch counts and farm
widths, and extend it with a three-term roofline (compute / HBM / ICI).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import warnings
from typing import Any, Dict, Optional, Sequence


# --------------------------------------------------------------------------
# Paper Sec. 13 algebra
# --------------------------------------------------------------------------
def farm_time(m_tasks: int, t_task: float, nw: int,
              t_emit: float = 0.0, t_collect: float = 0.0) -> float:
    """Completion time of m tasks on an nw-worker farm: workers process in
    parallel, but the emitter/collector are serial stages — the farm's
    service time is max(t_emit, t_task/nw, t_collect)."""
    service = max(t_emit, t_task / nw, t_collect)
    return m_tasks * service + t_task  # + one task latency (paper: latency
    # of a single task does not decrease)


def farm_speedup(m_tasks: int, t_task: float, nw: int,
                 t_emit: float = 0.0, t_collect: float = 0.0) -> float:
    return (m_tasks * t_task) / farm_time(m_tasks, t_task, nw, t_emit, t_collect)


def pipeline_service_time(stage_times: Sequence[float]) -> float:
    return max(stage_times)


def pipeline_time(m_tasks: int, stage_times: Sequence[float]) -> float:
    """m x T_S plus the fill latency sum(T_Si)."""
    return m_tasks * pipeline_service_time(stage_times) + sum(stage_times)


def pipeline_speedup(stage_times: Sequence[float], m_tasks: int = 10**9) -> float:
    """-> sum T_Si / max T_Si for long streams (paper's formula)."""
    seq = sum(stage_times)
    return (m_tasks * seq) / pipeline_time(m_tasks, stage_times) * (1.0)


def amdahl(serial_fraction: float, n: int) -> float:
    return 1.0 / (serial_fraction + (1.0 - serial_fraction) / n)


def choose_farm_width(t_task: float, n_max: int, t_emit: float = 0.0,
                      t_collect: float = 0.0,
                      overhead: float = 2e-5) -> int:
    """Smallest worker count whose per-item service time hits the farm's
    serial floor: service = max(t_emit, t_task/nw, t_collect), so adding
    workers beyond t_task/floor buys nothing (paper Sec. 13).  ``overhead``
    is the channel's own service time (queue push/pop) — the floor even for
    a free emitter.  Used by the graph compiler's ``place`` stage."""
    floor = max(t_emit, t_collect, overhead, 1e-9)
    w = math.ceil(t_task / floor)
    return max(1, min(w, max(1, n_max)))


def a2a_service_time(t_left: float, t_right: float, n_left: int,
                     n_right: int, hop: float = 0.0) -> float:
    """Steady-state per-item service time of an ``all_to_all`` stage: the
    left and right ranks pipeline across the lane grid, so the stage's
    service time is the slower side over its width — floored by twice the
    per-item channel hop, because the hosting node pays the emitter-side
    push and the collector-side pop serially for every item.  Used by the
    compiler's ``place`` to cost the process-tier a2a against the
    GIL-serialized thread estimate."""
    return max(t_left / max(1, n_left), t_right / max(1, n_right),
               2.0 * hop)


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble: (S-1)/(M+S-1) — the fill/drain idle fraction of the
    device pipeline skeleton."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def choose_microbatches(n_stages: int, max_bubble: float = 0.1,
                        max_micro: int = 256) -> int:
    """Smallest M with bubble fraction <= max_bubble."""
    m = math.ceil((n_stages - 1) * (1.0 - max_bubble) / max_bubble)
    return max(1, min(m, max_micro))


# --------------------------------------------------------------------------
# TPU v5e peaks per chip (Google Cloud documentation, "TPU v5e")
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float   # per chip, FLOP/s
    hbm_bw: float            # per chip, B/s
    ici_bw: float            # per link, B/s
    dci_bw: float            # per pod-to-pod link share, B/s
    hbm_bytes: float


TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    dci_bw=6.25e9,   # conservative DCI share per chip
    hbm_bytes=16 * 2**30,
)


@dataclasses.dataclass
class RooflineTerms:
    """The three terms, in seconds, per step, per chip (the prompt's
    definitions: totals divided by (chips x peak))."""
    compute_s: float
    memory_s: float
    collective_s: float
    # breakdown
    flops_total: float = 0.0
    bytes_total: float = 0.0
    coll_bytes_ici: float = 0.0
    coll_bytes_dci: float = 0.0
    model_flops: float = 0.0
    model_flops_s: float = 0.0   # time to run MODEL_FLOPS at peak

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Optimistic (perfect-overlap) step time = max of terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute fraction: MODEL_FLOPS-at-peak time / step-time."""
        if self.step_time_s == 0 or not self.model_flops:
            return 0.0
        return self.model_flops_s / self.step_time_s


def roofline(flops_total: float, bytes_total: float,
             coll_bytes_ici_per_chip: float, n_chips: int,
             hw: HardwareSpec = TPU_V5E,
             coll_bytes_dci_per_chip: float = 0.0,
             model_flops: float = 0.0) -> RooflineTerms:
    """flops_total/bytes_total are fleet totals (sum over chips); collective
    bytes are per-chip link traffic (ring-model)."""
    compute_s = flops_total / (n_chips * hw.peak_flops_bf16)
    memory_s = bytes_total / (n_chips * hw.hbm_bw)
    collective_s = (coll_bytes_ici_per_chip / hw.ici_bw
                    + coll_bytes_dci_per_chip / hw.dci_bw)
    return RooflineTerms(
        compute_s, memory_s, collective_s,
        flops_total=flops_total, bytes_total=bytes_total,
        coll_bytes_ici=coll_bytes_ici_per_chip,
        coll_bytes_dci=coll_bytes_dci_per_chip,
        model_flops=model_flops,
        model_flops_s=model_flops / (n_chips * hw.peak_flops_bf16))


# --------------------------------------------------------------------------
# Startup calibration — measured host constants for the compiler's place pass
# --------------------------------------------------------------------------
@dataclasses.dataclass
class HostCalibration:
    """The host-tier cost constants ``place`` consumes.  ``source`` records
    where they came from: baked-in ``default``s, a fresh ``measured`` run, or
    the on-disk ``cached`` result of an earlier run on this machine."""

    peak_flops: float           # useful numpy FLOP/s of one host core
    queue_hop_s: float          # per-item thread-tier SPSC push+pop cost
    proc_hop_s: float           # per-item process-lane (shm ring) hop cost
    device_dispatch_s: float    # per-microbatch host<->device boundary cost
    net_hop_s: float = 5e-4     # per-item network-lane (TCP frame) hop cost
    # marginal per-stage cost of one extra stage INSIDE a fused (single-jit)
    # device segment: what an adjacent device stage pays once core/fuse.py
    # has merged it into the run, vs. the full device_dispatch_s it would
    # pay as its own program.  Measured as (t_chain(K) - t_chain(1))/(K-1)
    # on jitted stage chains; typically ~0 (XLA fuses the bodies), which is
    # exactly why place() should amortize the one real dispatch across the
    # whole fused run.
    fused_segment_s: float = 2e-6
    # per-item cost of the *vectored* process lane (push_many/pop_many
    # amortize the index traffic and the pickling over a batch) — what the
    # batched farm transport actually pays per item
    shm_batched_hop_s: float = 5e-5
    # streaming bandwidth of the slab arena (oversize-ndarray path), GB/s
    arena_bw_gbs: float = 2.0
    # host<->device boundary transfer bandwidths (GB/s): what one microbatch
    # pays to cross the boundary each way.  With the overlapped boundary
    # (double-buffered async device_put / copy-out) these are what place()
    # charges AGAINST compute, not in addition to it.
    h2d_bw_gbs: float = 8.0
    d2h_bw_gbs: float = 8.0
    # overlap efficiency of the async boundary: 1.0 = transfers hide
    # perfectly behind compute (cost = max(transfer, compute)), 0.0 = no
    # overlap at all (cost = transfer + compute).  Measured by timing a
    # depth-K in-flight dispatch window against K synchronous round trips.
    overlap_eff: float = 0.5
    source: str = "default"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def proc_hop_effective_s(self) -> float:
        """The per-item process-lane cost placement should charge.  The
        farm transport is batched, so the amortized hop is the honest
        per-item price; capped by ``proc_hop_s`` so a noisy batched probe
        can never make the process tier look *worse* than per-item."""
        return min(self.proc_hop_s, self.shm_batched_hop_s)

    def boundary_time(self, transfer_s: float, compute_s: float) -> float:
        """Cost of one fused device run behind the *overlapped* boundary:
        the async window hides ``overlap_eff`` of the smaller term behind
        the larger one, so the run costs ``max(transfer, compute)`` plus
        the unhidden remainder — never their plain sum (the synchronous
        boundary's price), never better than the larger term alone."""
        lo, hi = min(transfer_s, compute_s), max(transfer_s, compute_s)
        eff = min(1.0, max(0.0, self.overlap_eff))
        return hi + (1.0 - eff) * lo


# conservative fallbacks, used only until/unless calibrate() has run
DEFAULT_CALIBRATION = HostCalibration(
    peak_flops=5e10, queue_hop_s=2e-5, proc_hop_s=2e-4,
    device_dispatch_s=2e-5, net_hop_s=5e-4, fused_segment_s=2e-6,
    shm_batched_hop_s=5e-5, arena_bw_gbs=2.0,
    h2d_bw_gbs=8.0, d2h_bw_gbs=8.0, overlap_eff=0.5, source="default")

# version 5: h2d_bw_gbs/d2h_bw_gbs + overlap_eff (the overlapped device
# boundary); version 4: fused_segment_s (device-segment fusion); version 3:
# shm_batched_hop_s + arena_bw_gbs joined (the batched uSPSC transport);
# version 2 added net_hop_s — older caches must miss cleanly
_CALIB_VERSION = 5
_calibration: Optional[HostCalibration] = None


def cache_key() -> Dict[str, Any]:
    """What every cached record was measured on: the cache format version,
    the host's core count and the JAX backend's first device.  A record
    whose key differs is a miss, so constants measured on one backend (the
    CPU, say) never steer placement on another (a TPU)."""
    import jax
    dev = jax.devices()[0]
    return {"version": _CALIB_VERSION, "cpu_count": os.cpu_count(),
            "platform": dev.platform, "device_kind": dev.device_kind}


def _key_matches(d: Any) -> bool:
    """Does a loaded cache dict carry this process's :func:`cache_key`?"""
    if not isinstance(d, dict):
        return False
    return all(d.get(k) == v for k, v in cache_key().items())


def _calib_cache_path() -> str:
    """Resolution order: ``REPRO_FF_CALIB_CACHE`` (exact file path) >
    ``REPRO_FF_CACHE`` (cache *directory* for everything this framework
    persists — what CI sets per job so runs are hermetic and the
    calibration can be pre-warmed once instead of re-measured in every
    pytest worker) > ``XDG_CACHE_HOME`` > ``~/.cache``."""
    override = os.environ.get("REPRO_FF_CALIB_CACHE")
    if override:
        return override
    base = os.environ.get("REPRO_FF_CACHE")
    if base:
        return os.path.join(base, "calibration.json")
    xdg = os.environ.get("XDG_CACHE_HOME",
                         os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(xdg, "repro_ff", "calibration.json")


def _measure_peak_flops() -> float:
    import numpy as np
    n = 192
    a = np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)
    flops = 2.0 * n ** 3
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        a @ a
        best = min(best, time.perf_counter() - t0)
    return flops / max(best, 1e-9)


def _measure_queue_hop() -> float:
    from .queues import SPSCQueue
    q = SPSCQueue(256)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        q.try_push(i)
        q.try_pop()
    return max((time.perf_counter() - t0) / n, 1e-9)


def _echo_main(in_lane, out_lane) -> None:
    """Calibration child: bounce items straight back (proc-lane hop probe)."""
    from .node import EOS
    while True:
        item = in_lane.pop()
        if item is EOS:
            break
        out_lane.push(item)
    out_lane.push_eos()


def _measure_proc_hop(n: int = 200) -> float:
    import numpy as np
    from .process import _mp_context, _quiet_fork
    from .shm import ShmSPSCQueue
    ping = ShmSPSCQueue(capacity=16)
    pong = ShmSPSCQueue(capacity=16)
    proc = _mp_context().Process(target=_echo_main, args=(ping, pong),
                                 daemon=True, name="ff-calibrate-echo")
    with _quiet_fork():
        proc.start()
    payload = np.arange(64, dtype=np.float32)
    try:
        ping.push(payload, timeout=5.0)         # warm both directions
        pong.pop(timeout=5.0)
        # streaming, not ping-pong: the farm emitter pushes a stream while
        # the collector drains, so the relevant hop cost is the pipelined
        # per-item cost, not the one-item round-trip latency.  Items ride
        # bare, like the farm protocol, so this measures the raw-slab path.
        sent = recv = 0
        deadline = time.monotonic() + 10.0
        t0 = time.perf_counter()
        while recv < n:
            progressed = False
            if sent < n and ping.try_push(payload):
                sent += 1
                progressed = True
            ok, _ = pong.try_pop()
            if ok:
                recv += 1
                progressed = True
            if not progressed:
                if time.monotonic() > deadline:
                    raise TimeoutError("proc-hop calibration stalled")
                time.sleep(1e-6)
        rtt = 2.0 * (time.perf_counter() - t0) / n  # keep rtt/2 == per hop
    finally:
        try:
            ping.push_eos(timeout=1.0)
        except TimeoutError:
            pass
        proc.join(timeout=5.0)
        if proc.is_alive():
            proc.terminate()
        ping.destroy()
        pong.destroy()
    return max(rtt / 2.0, 1e-9)


def _echo_many_main(in_lane, out_lane, batch: int) -> None:
    """Calibration child: bounce items back in vectored batches (batched
    proc-lane hop probe — same pop_many/push_many path the farm workers use)."""
    from .node import EOS
    done = False
    while not done:
        out = []
        for item, _seq in in_lane.pop_many(batch):
            if item is EOS:
                done = True
                break
            out.append(item)
        if out:
            out_lane.push_many(out)
    out_lane.push_eos()


def _measure_shm_batched_hop(n: int = 2000, batch: int = 32) -> float:
    """Per-item cost of the *vectored* process lane: same streaming echo
    shape as :func:`_measure_proc_hop`, but both sides move items with
    ``try_push_many``/``try_pop_many`` so the index traffic and the pickling
    amortize over the batch.  This is what a batched farm hop actually costs
    per item, and what ``place`` should charge for the process tier."""
    from .process import _mp_context, _quiet_fork
    from .shm import ShmSPSCQueue
    ping = ShmSPSCQueue(capacity=64)
    pong = ShmSPSCQueue(capacity=64)
    proc = _mp_context().Process(target=_echo_many_main,
                                 args=(ping, pong, batch),
                                 daemon=True, name="ff-calibrate-echo-many")
    with _quiet_fork():
        proc.start()
    items = list(range(batch))                  # small items: the batch win
    try:
        ping.push_many(items, timeout=5.0)      # warm both directions
        got = 0
        deadline = time.monotonic() + 5.0
        while got < batch:
            got += len(pong.try_pop_many(batch))
            if time.monotonic() > deadline:
                raise TimeoutError("batched-hop calibration warmup stalled")
        sent = recv = 0
        deadline = time.monotonic() + 10.0
        t0 = time.perf_counter()
        while recv < n:
            progressed = False
            if sent < n:
                k = ping.try_push_many(items[:min(batch, n - sent)])
                sent += k
                progressed = progressed or k > 0
            k = len(pong.try_pop_many(batch))
            recv += k
            progressed = progressed or k > 0
            if not progressed:
                if time.monotonic() > deadline:
                    raise TimeoutError("batched-hop calibration stalled")
                time.sleep(1e-6)
        rtt = 2.0 * (time.perf_counter() - t0) / n  # keep rtt/2 == per hop
    finally:
        try:
            ping.push_eos(timeout=1.0)
        except TimeoutError:
            pass
        proc.join(timeout=5.0)
        if proc.is_alive():
            proc.terminate()
        ping.destroy()
        pong.destroy()
    return max(rtt / 2.0, 1e-9)


def _measure_arena_bw(nbytes: int = 4 << 20, reps: int = 5) -> float:
    """Streaming bandwidth (GB/s) of the slab-arena path: one oversize
    ndarray through an arena-backed lane per rep (producer copy in + consumer
    copy out), in-process so it measures memory bandwidth, not scheduling."""
    import numpy as np
    from .shm import ShmSPSCQueue
    q = ShmSPSCQueue(capacity=4, slot_bytes=1024, arena_bytes=2 * nbytes)
    try:
        a = np.zeros(nbytes // 4, dtype=np.float32)
        q.try_push(a)                           # warm the mappings
        q.try_pop()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            if not q.try_push(a):
                break
            ok, _ = q.try_pop()
            if not ok:
                break
            best = min(best, time.perf_counter() - t0)
        if not (best < float("inf")) or q.arena_pushes == 0:
            return DEFAULT_CALIBRATION.arena_bw_gbs
        return max(nbytes / best / 1e9, 1e-3)
    finally:
        q.destroy()


def _measure_net_hop(n: int = 200) -> float:
    """Per-item network-lane hop cost, measured over loopback TCP with the
    actual frame codec of ``core/net.py`` (raw-ndarray fast path).  Streamed
    pipelined like :func:`_measure_proc_hop` — the remote farm's emitter and
    collector overlap, so the relevant figure is the per-item cost of a full
    round trip divided by two, not one-frame latency."""
    import socket
    import struct
    import threading

    import numpy as np
    try:
        from .net import (TAG_EOS, decode_payload, encode_frame, encode_item,
                          read_frame)
        from .shm import _SLOT_FMT
        ls = socket.create_server(("127.0.0.1", 0))
        port = ls.getsockname()[1]

        def _echo() -> None:
            conn, _peer = ls.accept()
            try:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                while True:
                    fr = read_frame(conn)
                    if fr is None or fr[0] == TAG_EOS:
                        return
                    tag, payload, seq = fr
                    conn.sendall(struct.pack(_SLOT_FMT, len(payload),
                                             tag, seq) + payload)
            finally:
                conn.close()

        echo = threading.Thread(target=_echo, daemon=True,
                                name="ff-calibrate-net-echo")
        echo.start()
        sock = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        frame = encode_item(np.arange(64, dtype=np.float32))
        try:
            sock.sendall(frame)                 # warm both directions
            read_frame(sock)
            t0 = time.perf_counter()

            def _send() -> None:
                for _ in range(n):
                    sock.sendall(frame)

            sender = threading.Thread(target=_send, daemon=True)
            sender.start()
            for _ in range(n):
                tag, payload, _seq = read_frame(sock)
                decode_payload(tag, payload)
            rtt = (time.perf_counter() - t0) / n
            sender.join(timeout=5.0)
            sock.sendall(encode_frame(TAG_EOS))
        finally:
            sock.close()
            ls.close()
            echo.join(timeout=5.0)
        return max(rtt / 2.0, 1e-9)
    except Exception:   # noqa: BLE001 - no loopback here: keep the default
        return DEFAULT_CALIBRATION.net_hop_s


def _measure_device_dispatch() -> float:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    jax.block_until_ready(f(x))             # compile outside the clock
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        best = min(best, time.perf_counter() - t0)
    return max(best, 1e-9)


def _measure_fused_segment(k: int = 4) -> float:
    """Marginal per-stage cost inside one jitted device segment: time a
    ``k``-stage composed chain vs a 1-stage program and divide the extra
    by ``k - 1``.  Near-zero on every real backend (XLA fuses the bodies) —
    which is the measured fact that lets ``place`` charge a fused run one
    dispatch instead of one per stage."""
    import jax
    import jax.numpy as jnp

    def _chain(n):
        def f(x):
            for i in range(n):
                x = x * 1.0001 + float(i)
            return x
        return jax.jit(f)

    x = jnp.zeros((8,), jnp.float32)

    def _best(f):
        jax.block_until_ready(f(x))         # compile outside the clock
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            best = min(best, time.perf_counter() - t0)
        return best

    t1, tk = _best(_chain(1)), _best(_chain(k))
    return max((tk - t1) / (k - 1), 1e-9)


def _measure_h2d_bw(nbytes: int = 4 << 20, reps: int = 5) -> float:
    """Host->device boundary bandwidth (GB/s): one device_put of an
    ``nbytes`` float32 array, synced, best of ``reps`` — the per-microbatch
    input cost of the device boundary node."""
    import jax
    import numpy as np
    a = np.zeros(nbytes // 4, dtype=np.float32)
    jax.block_until_ready(jax.device_put(a))    # warm the path
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(a))
        best = min(best, time.perf_counter() - t0)
    return max(nbytes / max(best, 1e-9) / 1e9, 1e-3)


def _measure_d2h_bw(nbytes: int = 4 << 20, reps: int = 5) -> float:
    """Device->host boundary bandwidth (GB/s): one full host copy-out of an
    ``nbytes`` device array, best of ``reps`` — the per-microbatch output
    cost of the device boundary node."""
    import jax
    import numpy as np
    x = jax.block_until_ready(
        jax.device_put(np.zeros(nbytes // 4, dtype=np.float32)))
    np.asarray(x)                               # warm the path
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(x)
        best = min(best, time.perf_counter() - t0)
    return max(nbytes / max(best, 1e-9) / 1e9, 1e-3)


def _measure_overlap_eff(k: int = 8, reps: int = 3) -> float:
    """Overlap efficiency of JAX's async dispatch on this backend: time
    ``k`` jitted steps submitted as one in-flight window (sync only at the
    end) against the same ``k`` steps each synced before the next is
    submitted.  1 - window/serial is the fraction of per-step host round
    trips the window hides; clamped to [0, 1].  A backend with synchronous
    dispatch measures ~0 and place() falls back to costing the boundary as
    transfer + compute."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 1.0001 + 1.0)
    x = jnp.zeros((256, 256), jnp.float32)
    jax.block_until_ready(f(x))                 # compile off the clock
    serial = window = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _i in range(k):
            jax.block_until_ready(f(x))
        serial = min(serial, time.perf_counter() - t0)
        t0 = time.perf_counter()
        ys = [f(x) for _i in range(k)]
        jax.block_until_ready(ys)
        window = min(window, time.perf_counter() - t0)
    if serial <= 0.0 or not (serial < float("inf")):
        return DEFAULT_CALIBRATION.overlap_eff
    return min(1.0, max(0.0, 1.0 - window / serial))


def calibrate(cache: bool = True) -> HostCalibration:
    """Measure the host-tier cost constants on this machine and (optionally)
    persist them, replacing the baked-in defaults ``place`` would otherwise
    consume: one core's useful numpy FLOP/s, the per-item thread-queue hop,
    the per-item shared-memory process-lane hop, the per-item loopback
    network-lane hop, the host<->device dispatch cost, the boundary
    transfer bandwidths each way (h2d/d2h), and the async-dispatch overlap
    efficiency the overlapped boundary can bank on.

    A read-only or unwritable cache location (containerized remote workers,
    sealed CI sandboxes) degrades to in-memory constants with a one-line
    warning — never an exception.  A device probe that fails raises: a
    broken backend must not hide behind the baked-in defaults."""
    global _calibration
    c = HostCalibration(
        peak_flops=_measure_peak_flops(),
        queue_hop_s=_measure_queue_hop(),
        proc_hop_s=_measure_proc_hop(),
        device_dispatch_s=_measure_device_dispatch(),
        net_hop_s=_measure_net_hop(),
        fused_segment_s=_measure_fused_segment(),
        shm_batched_hop_s=_measure_shm_batched_hop(),
        arena_bw_gbs=_measure_arena_bw(),
        h2d_bw_gbs=_measure_h2d_bw(),
        d2h_bw_gbs=_measure_d2h_bw(),
        overlap_eff=_measure_overlap_eff(),
        source="measured")
    _calibration = c
    if cache:
        path = _calib_cache_path()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump({**cache_key(), **c.as_dict()}, f)
        except OSError as e:
            warnings.warn(
                f"perf_model: calibration cache {path!r} is not writable "
                f"({e}); keeping measured constants in memory only",
                RuntimeWarning, stacklevel=2)
    return c


def _load_cached_calibration() -> Optional[HostCalibration]:
    try:
        with open(_calib_cache_path()) as f:
            d = json.load(f)
        if not _key_matches(d):
            return None
        return HostCalibration(
            peak_flops=float(d["peak_flops"]),
            queue_hop_s=float(d["queue_hop_s"]),
            proc_hop_s=float(d["proc_hop_s"]),
            device_dispatch_s=float(d["device_dispatch_s"]),
            net_hop_s=float(d["net_hop_s"]),
            fused_segment_s=float(d["fused_segment_s"]),
            shm_batched_hop_s=float(d["shm_batched_hop_s"]),
            arena_bw_gbs=float(d["arena_bw_gbs"]),
            h2d_bw_gbs=float(d["h2d_bw_gbs"]),
            d2h_bw_gbs=float(d["d2h_bw_gbs"]),
            overlap_eff=float(d["overlap_eff"]),
            source="cached")
    except (OSError, ValueError, KeyError, TypeError):
        # any unreadable/corrupt cache is a miss, never a crash
        return None


def get_calibration(measure: bool = True) -> HostCalibration:
    """The process-wide calibration: memoized, then the on-disk cache, then a
    fresh :func:`calibrate` run (skipped when ``measure=False``, which
    returns the baked-in defaults instead)."""
    global _calibration
    if _calibration is not None:
        return _calibration
    cached = _load_cached_calibration()
    if cached is not None:
        _calibration = cached
        return cached
    if not measure:
        return DEFAULT_CALIBRATION
    return calibrate()


def reset_calibration() -> None:
    """Drop the in-memory calibration (tests)."""
    global _calibration
    _calibration = None


# --------------------------------------------------------------------------
# Online refinement — runner stats fed back into the calibration cache
# --------------------------------------------------------------------------
# ``calibrate()`` made the place() constants measured-at-startup instead of
# baked-in; ``observe()`` closes the remaining gap: runtime stats (sampled by
# core/runtime.Supervisor, or passed in by hand) refine BOTH the channel
# constants (shared-memory hop EMA) and a per-callable table of measured
# service times + GIL signals, so the *next* compile()'s annotate/place pass
# starts from what actually happened rather than a fresh sample probe.
# The table is keyed by ``fn_key`` (module.qualname — stable across runs of
# the same code, best-effort across edits) and persists inside the same
# on-disk calibration cache.

_OBSERVE_MIN_ITEMS = 8      # ignore records with fewer processed items
_observed: Optional[Dict[str, dict]] = None


def fn_key(fn) -> Optional[str]:
    """Stable-ish identity for a worker callable in the observed-cost table
    (``module.qualname``).  None for objects without one (partials, odd
    callables) — those simply never match an observation."""
    mod = getattr(fn, "__module__", None)
    qn = getattr(fn, "__qualname__", None)
    if not mod or not qn:
        return None
    return f"{mod}.{qn}"


def _load_observed() -> Dict[str, dict]:
    global _observed
    if _observed is None:
        _observed = {}
        try:
            with open(_calib_cache_path()) as f:
                d = json.load(f)
            obs = d.get("observed")
            if isinstance(obs, dict) and _key_matches(d):
                _observed = {str(k): dict(v) for k, v in obs.items()
                             if isinstance(v, dict)}
        except (OSError, ValueError, TypeError):
            pass
    return _observed


def lookup_observed(key: Optional[str],
                    min_items: int = _OBSERVE_MIN_ITEMS) -> Optional[dict]:
    """The observed cost record for a callable key, or None when there is no
    (sufficiently substantiated) history.  Consumed by the compiler's
    ``annotate`` stage: a callable with runtime history no longer needs a
    ``sample=`` probe to be cost-placed."""
    if not key:
        return None
    rec = _load_observed().get(key)
    if rec and rec.get("items", 0) >= min_items \
            and float(rec.get("t_task", 0.0)) > 0.0:
        return dict(rec)
    return None


def reset_observed() -> None:
    """Drop the in-memory observed-cost table (tests)."""
    global _observed
    _observed = None


def _save_cache_tables(what: str = "observed costs") -> None:
    """Persist the calibration + observed tables into the one cache file; a
    read-only location degrades to in-memory with a warning."""
    path = _calib_cache_path()
    c = get_calibration(measure=False)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({**cache_key(), **c.as_dict(),
                       "observed": _load_observed()}, f)
    except OSError as e:
        warnings.warn(
            f"perf_model: calibration cache {path!r} is not writable ({e}); "
            f"keeping {what} in memory only",
            RuntimeWarning, stacklevel=2)


def _save_observed() -> None:
    _save_cache_tables("observed costs")


def _stat_records(x, out: list) -> None:
    """Collect node-stat dicts from an arbitrarily nested stats() tree."""
    if isinstance(x, dict):
        if "svc_cpu_ema_s" in x or "hop_ema_s" in x or "fn_key" in x:
            out.append(x)
        for v in x.values():
            _stat_records(v, out)
    elif isinstance(x, (list, tuple)):
        for v in x:
            _stat_records(v, out)


def observe(stats: dict, alpha: float = 0.25, write: bool = False) -> int:
    """Fold one ``runner.stats()`` snapshot (or any nested stats tree) into
    the calibration state; returns the number of facts absorbed.

    - farm records carrying a ``fn_key`` and a per-item CPU-time EMA update
      the observed per-callable service time — thread-tier records from the
      parent's own measurement, process/remote-tier records from the
      worker-side :class:`~repro.core.shm.WorkerStats` CPU clocks shipped
      back over the result lanes (true service times, so the Supervisor's
      process->thread policy no longer needs the hop-domination heuristic);
      a thread record's ``gil_ratio`` (CPU/wall) measured under >=2
      concurrently active workers also settles the callable's GIL signal —
      below 0.7 the workers were serializing on the GIL
      (``releases_gil=False``), above 0.9 they truly ran in parallel
      (``True``);
    - process-tier records with a parent-side ``hop_ema_s`` refine the
      calibrated shared-memory lane hop with an EMA; remote-tier records
      refine the network-lane hop (``net_hop_s``) the same way.

    ``write=True`` persists the refreshed calibration + observed table into
    the on-disk cache (the supervisor writes once at ``stop()``; periodic
    in-memory merges stay cheap)."""
    global _calibration
    recs: list = []
    _stat_records(stats, recs)
    table = _load_observed()
    absorbed = 0
    for r in recs:
        items = int(r.get("items", 0) or 0)
        if items < _OBSERVE_MIN_ITEMS:
            continue
        key = r.get("fn_key")
        cpu = float(r.get("svc_cpu_ema_s", 0.0) or 0.0)
        backend = r.get("backend")
        if key and cpu > 0.0 and backend in ("thread", "process", "remote"):
            prev = table.get(key)
            rg = prev.get("releases_gil") if prev else None
            ratio = r.get("gil_ratio")     # thread records only
            if ratio is not None and int(r.get("active", 1) or 1) >= 2:
                if ratio < 0.7:
                    rg = False
                elif ratio > 0.9:
                    rg = True
            t = cpu if prev is None else \
                (1.0 - alpha) * float(prev["t_task"]) + alpha * cpu
            table[key] = {"t_task": t, "releases_gil": rg,
                          "items": max(items, prev["items"] if prev else 0)}
            absorbed += 1
        hop = float(r.get("hop_ema_s", 0.0) or 0.0)
        if hop > 0.0 and backend in ("process", "remote"):
            c = get_calibration(measure=False)
            if backend == "process":
                c = dataclasses.replace(
                    c, proc_hop_s=(1.0 - alpha) * c.proc_hop_s + alpha * hop,
                    source="observed")
            else:
                c = dataclasses.replace(
                    c, net_hop_s=(1.0 - alpha) * c.net_hop_s + alpha * hop,
                    source="observed")
            _calibration = c
            absorbed += 1
    if write and absorbed:
        _save_observed()
    return absorbed
