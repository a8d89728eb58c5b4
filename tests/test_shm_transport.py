"""The batched uSPSC shm transport (PR 7): vectored push_many/pop_many
batch-boundary correctness, the uSPSC unbounded tier, the slab arena for
oversize ndarrays, compile(transport=...) tuning knobs, NUMA degradation on
a single-node container, and the amortized-hop calibration constants."""

import os
import signal
import time

import numpy as np
import pytest

from repro.core import (FFNode, ProcessRunner, WorkerCrashed, farm,
                        perf_model as pm, pipeline)
from repro.core.process import (_node_affinity, _numa_topology,
                                _parse_cpulist, _pin)
from repro.core.queues import QueueClosed
from repro.core.shm import (BatchedLaneWriter, ShmArena, ShmError,
                            ShmSPSCQueue, ShmUSPSCQueue, TransportConfig,
                            as_transport)


class Gen(FFNode):
    def __init__(self, n):
        super().__init__()
        self.i, self.n = 0, n

    def svc(self, _):
        self.i += 1
        return np.float32(self.i) if self.i <= self.n else None


# -- vectored push/pop batch boundaries ----------------------------------------
def test_push_many_partial_flushes_preserve_exact_order():
    # ring far smaller than the stream: every push_many is a partial flush
    q = ShmSPSCQueue(capacity=4)
    items = [(i, f"s{i}") for i in range(257)]   # odd count: partial tail
    sent = 0
    out = []
    while sent < len(items) or len(out) < len(items):
        sent += q.try_push_many(items[sent:sent + 16])
        out.extend(item for item, _seq in q.try_pop_many(8))
    assert out == items
    q.destroy()


def test_push_many_assigns_contiguous_seqs_across_partial_flushes():
    q = ShmSPSCQueue(capacity=4)
    seqs = []
    sent = 0
    while sent < 40 or len(seqs) < 40:
        sent += q.try_push_many(list(range(sent, min(40, sent + 7))),
                                seqs=list(range(sent, min(40, sent + 7))))
        seqs.extend(s for _item, s in q.try_pop_many(5))
    assert seqs == list(range(40))
    q.destroy()


def test_eos_after_pending_partial_batch_arrives_last():
    from repro.core.node import EOS
    q = ShmSPSCQueue(capacity=32)
    w = BatchedLaneWriter(q, batch=16, flush_s=60.0)
    for i in range(5):                  # pending partial batch, never due
        w.put(i, seq=i)
    assert q.empty()                    # nothing flushed yet
    w.push_eos()                        # must flush the 5, THEN mark EOS
    got = [item for item, _ in q.try_pop_many(64)]
    assert got[:5] == [0, 1, 2, 3, 4]   # items strictly before the mark
    assert got[5] is EOS and len(got) == 6
    q.destroy()


def test_err_after_pending_partial_batch_arrives_after_items():
    q = ShmSPSCQueue(capacity=32)
    w = BatchedLaneWriter(q, batch=16, flush_s=60.0)
    for i in range(3):
        w.put(i, seq=i)
    w.push_err(ShmError(0, "ValueError: boom", "tb"))
    got = [q.pop() for _ in range(3)]
    assert got == [0, 1, 2]
    err = q.pop()
    assert isinstance(err, ShmError) and "ValueError" in err.exc
    q.destroy()


def test_batched_writer_age_flush():
    q = ShmSPSCQueue(capacity=32)
    w = BatchedLaneWriter(q, batch=16, flush_s=0.01)
    w.put("x", seq=0)
    assert q.empty()
    deadline = time.monotonic() + 5.0
    while q.empty():
        w.maybe_flush()
        if time.monotonic() > deadline:
            pytest.fail("age flush never fired")
        time.sleep(1e-3)
    assert q.pop() == "x"
    q.destroy()


# -- uSPSC unbounded tier ------------------------------------------------------
def test_uspsc_grows_segments_on_stream_far_beyond_capacity():
    q = ShmUSPSCQueue(capacity=8)
    n = 500                             # >> one 8-slot segment
    for i in range(n):                  # never blocks: the chain grows
        q.push(i, timeout=1.0)
    assert q.segments_grown > 0
    assert [q.pop() for _ in range(n)] == list(range(n))
    q.destroy()


def test_uspsc_push_many_grows_and_preserves_order():
    # ndarrays take one slot each (no batch coalescing), so 300 of them
    # must span many 8-slot segments within the single push_many call
    q = ShmUSPSCQueue(capacity=8)
    items = [np.full(4, i, dtype=np.int64) for i in range(300)]
    q.push_many(items, timeout=5.0)     # single call spans many segments
    assert q.segments_grown > 0
    out = []
    while len(out) < len(items):
        out.extend(item for item, _ in q.pop_many(64, timeout=5.0))
    assert [int(a[0]) for a in out] == list(range(300))
    q.destroy()


def test_uspsc_push_many_coalesces_small_items_without_growth():
    # the flip side: runs of small non-array items pickle together into
    # BATCH slots, so even 300 of them fit one 8-slot segment
    q = ShmUSPSCQueue(capacity=8)
    items = [(i, "payload") for i in range(300)]
    q.push_many(items, timeout=5.0)
    assert q.segments_grown == 0
    out = []
    while len(out) < len(items):
        out.extend(item for item, _ in q.pop_many(512, timeout=5.0))
    assert out == items
    q.destroy()


def _uspsc_producer_child(q, n):
    for i in range(n):
        q.push(np.full(2, i, dtype=np.int64), timeout=30.0)
    q.push_eos()
    q.detach()


@pytest.mark.shm
def test_uspsc_cross_process_growth_and_order():
    import multiprocessing as mp
    from repro.core.node import EOS
    q = ShmUSPSCQueue(capacity=8)
    n = 400
    p = mp.get_context("fork").Process(
        target=_uspsc_producer_child, args=(q, n), daemon=True)
    p.start()
    out = []
    while True:                         # EOS rides in-stream, like a farm lane
        item = q.pop(timeout=30.0)
        if item is EOS:
            break
        out.append(int(item[0]))
    assert out == list(range(n))
    p.join(timeout=10.0)
    q.destroy()


def test_uspsc_close_drains_then_raises():
    q = ShmUSPSCQueue(capacity=4)
    for i in range(10):
        q.push(np.full(2, i, dtype=np.int64))
    q.close()                           # marks the producer's final segment
    assert [int(q.pop()[0]) for _ in range(10)] == list(range(10))
    with pytest.raises(QueueClosed):
        q.pop(timeout=1.0)
    q.destroy()


def test_spmc_unbounded_lanes_never_backpressure():
    from repro.core.shm import ShmSPMCQueue
    q = ShmSPMCQueue(2, capacity=4, bounded=False)
    for i in range(100):                # 50 items per 4-slot lane
        q.push_to(i % 2, i, timeout=1.0)    # never blocks: chains grow
    a = [q.lanes[0].pop() for _ in range(50)]
    b = [q.lanes[1].pop() for _ in range(50)]
    assert a == list(range(0, 100, 2))
    assert b == list(range(1, 100, 2))
    q.destroy()


# -- slab arena ----------------------------------------------------------------
def test_oversize_array_takes_arena_path_never_pickle():
    q = ShmSPSCQueue(capacity=8, slot_bytes=1024, arena_bytes=1 << 22)
    a = np.arange(65_536, dtype=np.float32)     # 256 KiB >> slot_bytes
    assert q.try_push(a)
    assert q.arena_pushes == 1
    assert q.pickle_fallbacks == 0              # the regression guard
    ok, out = q.try_pop()
    assert ok and np.array_equal(out, a) and out.dtype == a.dtype
    q.destroy()


def test_arena_frees_space_after_consumption():
    q = ShmSPSCQueue(capacity=8, slot_bytes=1024, arena_bytes=1 << 20)
    a = np.zeros(100_000, dtype=np.float32)     # 400 KiB of a 1 MiB arena
    for _ in range(8):                          # > arena capacity in total
        assert q.try_push(a)
        ok, _out = q.try_pop()
        assert ok
    assert q.arena_pushes == 8
    q.destroy()


def test_arena_backpressure_when_full_then_recovers():
    q = ShmSPSCQueue(capacity=8, slot_bytes=1024, arena_bytes=1 << 20)
    a = np.zeros(100_000, dtype=np.float32)
    assert q.try_push(a)
    assert q.try_push(a)
    assert not q.try_push(a)            # arena full: back-pressure, no pickle
    assert q.pickle_fallbacks == 0
    q.try_pop()
    assert q.try_push(a)                # freed space is reusable
    q.destroy()


def test_array_larger_than_whole_arena_raises():
    q = ShmSPSCQueue(capacity=8, slot_bytes=1024, arena_bytes=1 << 16)
    with pytest.raises(ValueError, match="arena_bytes"):
        q.try_push(np.zeros(1 << 20, dtype=np.uint8))
    q.destroy()


def test_arena_roundtrip_noncontiguous_and_fortran_arrays():
    q = ShmSPSCQueue(capacity=8, slot_bytes=512, arena_bytes=1 << 22)
    base = np.arange(40_000, dtype=np.float64).reshape(200, 200)
    for a in (base[::2, ::2], np.asfortranarray(base)):
        assert q.try_push(a)
        ok, out = q.try_pop()
        assert ok and np.array_equal(out, a)
    q.destroy()


def _arena_echo_child(in_lane, out_lane):
    from repro.core.node import EOS
    while True:
        item = in_lane.pop()
        if item is EOS:
            break
        out_lane.push(item)
    out_lane.push_eos()
    in_lane.detach()
    out_lane.detach()


@pytest.mark.shm
def test_arena_arrays_cross_process_roundtrip():
    import multiprocessing as mp
    ping = ShmSPSCQueue(capacity=8, slot_bytes=1024, arena_bytes=1 << 22)
    pong = ShmSPSCQueue(capacity=8, slot_bytes=1024, arena_bytes=1 << 22)
    p = mp.get_context("fork").Process(
        target=_arena_echo_child, args=(ping, pong), daemon=True)
    p.start()
    rng = np.random.default_rng(0)
    for _ in range(5):
        a = rng.standard_normal(50_000).astype(np.float32)  # 200 KiB
        ping.push(a, timeout=30.0)
        out = pong.pop(timeout=30.0)
        assert np.array_equal(out, a)
    assert ping.arena_pushes == 5 and ping.pickle_fallbacks == 0
    ping.push_eos()
    p.join(timeout=10.0)
    ping.destroy()
    pong.destroy()


# -- crashed worker mid-batch --------------------------------------------------
def _kill_on_five(x):
    if int(x) == 5:
        os.kill(os.getpid(), signal.SIGKILL)
    return float(x)


@pytest.mark.shm
def test_crashed_worker_mid_batch_surfaces_worker_crashed():
    # stream >> batch so the crash lands with batches pending on both the
    # emitter and collector sides; the farm must unwind, not wedge
    r = pipeline(Gen(200), farm(_kill_on_five, n=2)).compile(
        mode="process", transport={"batch": 16, "flush_s": 0.001})
    t0 = time.monotonic()
    with pytest.raises(WorkerCrashed):
        r.run(timeout=60.0)
    assert time.monotonic() - t0 < 45.0


# -- compile(transport=...) knobs ----------------------------------------------
def test_transport_config_defaults_and_validation():
    tc = TransportConfig()
    assert (tc.ring_slots, tc.grid_slots, tc.slot_bytes) == (64, 32, 1 << 16)
    assert tc.bounded and tc.batch == 16
    assert as_transport(None) == TransportConfig()
    assert as_transport({"ring_slots": 8}).ring_slots == 8
    assert as_transport(tc) is tc
    with pytest.raises(ValueError):
        TransportConfig(ring_slots=1)
    with pytest.raises(ValueError):
        TransportConfig(batch=0)
    with pytest.raises(TypeError):
        as_transport({"bogus_knob": 1})


@pytest.mark.shm
def test_compile_transport_dict_tunes_farm_lanes():
    r = pipeline(Gen(6), farm(lambda x: x * 2.0, n=2)).compile(
        mode="process",
        transport={"ring_slots": 8, "slot_bytes": 1 << 12, "batch": 4})
    assert isinstance(r, ProcessRunner)
    assert sorted(float(v) for v in r.run(timeout=60.0)) == [
        2.0 * i for i in range(1, 7)]


@pytest.mark.shm
def test_compile_transport_unbounded_worker_lanes():
    r = pipeline(Gen(50), farm(lambda x: x + 1.0, n=2)).compile(
        mode="process", transport=TransportConfig(ring_slots=4,
                                                  bounded=False))
    out = sorted(float(v) for v in r.run(timeout=60.0))
    assert out == [float(i) + 1.0 for i in range(1, 51)]


# -- NUMA degradation ----------------------------------------------------------
def test_parse_cpulist_forms():
    assert _parse_cpulist("0-3,8-11\n") == [0, 1, 2, 3, 8, 9, 10, 11]
    assert _parse_cpulist("0") == [0]
    assert _parse_cpulist("") == []


@pytest.mark.shm
def test_numa_degrades_gracefully_on_single_node_host():
    # the CI container has one (or zero) sysfs NUMA nodes: topology must
    # come back empty, pinning must fall back to round-robin cores, and the
    # affinity guard must be a no-op — never a crash
    nodes = _numa_topology(refresh=True)
    assert isinstance(nodes, list)
    saved = os.sched_getaffinity(0)
    try:
        _pin(0)                         # falls back to core round-robin
        _pin(7)
    finally:
        os.sched_setaffinity(0, saved)
    with _node_affinity([]):            # empty node set: no-op
        pass
    r = pipeline(Gen(6), farm(lambda x: x * 3.0, n=2)).compile(
        mode="process")
    assert sorted(float(v) for v in r.run(timeout=60.0)) == [
        3.0 * i for i in range(1, 7)]


# -- calibration: the amortized hop --------------------------------------------
def test_calibration_effective_hop_caps_at_per_item_hop():
    c = pm.HostCalibration(peak_flops=1e10, queue_hop_s=1e-5,
                           proc_hop_s=2e-4, device_dispatch_s=1e-5,
                           shm_batched_hop_s=1e-5)
    assert c.proc_hop_effective_s() == 1e-5
    noisy = pm.HostCalibration(peak_flops=1e10, queue_hop_s=1e-5,
                               proc_hop_s=2e-4, device_dispatch_s=1e-5,
                               shm_batched_hop_s=5e-4)
    assert noisy.proc_hop_effective_s() == 2e-4


@pytest.mark.shm
def test_measured_batched_hop_beats_per_item_hop():
    batched = pm._measure_shm_batched_hop(n=400, batch=32)
    per_item = pm._measure_proc_hop(n=100)
    assert 0.0 < batched < per_item


def test_calibration_cache_roundtrips_batched_constants(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_FF_CALIB_CACHE",
                       str(tmp_path / "calib.json"))
    import dataclasses
    import json
    c = dataclasses.replace(pm.DEFAULT_CALIBRATION,
                            shm_batched_hop_s=7e-6, arena_bw_gbs=3.5,
                            source="measured")
    with open(tmp_path / "calib.json", "w") as f:
        json.dump({**pm.cache_key(), **c.as_dict()}, f)
    loaded = pm._load_cached_calibration()
    assert loaded is not None
    assert loaded.shm_batched_hop_s == 7e-6
    assert loaded.arena_bw_gbs == 3.5
    # constants measured on another backend's device must miss
    with open(tmp_path / "calib.json", "w") as f:
        json.dump({**pm.cache_key(), **c.as_dict(),
                   "platform": "tpu", "device_kind": "TPU v5 lite"}, f)
    assert pm._load_cached_calibration() is None
    # version-2 caches (no batched constants) must miss cleanly
    with open(tmp_path / "calib.json", "w") as f:
        d = {"version": 2, "cpu_count": os.cpu_count(), **c.as_dict()}
        del d["shm_batched_hop_s"], d["arena_bw_gbs"]
        json.dump(d, f)
    assert pm._load_cached_calibration() is None


def test_stale_autotune_cache_is_ignored(tmp_path, monkeypatch, plan):
    """A calibration cache that an older tree wrote with an ``"autotune"``
    table of tile and window winners still loads its calibration, and
    nothing reads the table: the a2a block and the device boundary's
    in-flight window keep their defaults."""
    import dataclasses
    import json
    from repro.core.compiler import CompileConfig, _DeviceStageNode
    from repro.kernels.a2a_fused import _pick_block
    monkeypatch.delenv("REPRO_FF_CALIB_CACHE", raising=False)
    monkeypatch.setenv("REPRO_FF_CACHE", str(tmp_path))
    c = dataclasses.replace(pm.DEFAULT_CALIBRATION, queue_hop_s=7e-6)
    with open(tmp_path / "calibration.json", "w") as f:
        json.dump({**pm.cache_key(), **c.as_dict(), "observed": {},
                   "autotune": {"a2a_fused:T256:E2:D8": {"block_t": 16},
                                "device_overlap:window": {"inflight": 5}}},
                  f)
    pm.reset_calibration()
    try:
        assert _pick_block(256, None) == 128
        r = pipeline(lambda x: float(x), lambda x: x + 1.0).compile(
            config=CompileConfig(plan=plan, inflight=None, normalize=False,
                                 placements={0: "host", 1: "device"}))
        node = [s for s in r._skel._stages
                if isinstance(s, _DeviceStageNode)][0]
        assert node._inflight == 2
        out = r.run([np.float32(i) for i in range(5)])
        assert [float(np.asarray(y)) for y in out] == [
            i + 1.0 for i in range(5)]
        d = pipeline(lambda x: x + 1.0).compile(
            config=CompileConfig(plan=plan, mode="device", inflight=None))
        assert d._inflight == 2
        loaded = pm.get_calibration(measure=False)
        assert loaded.queue_hop_s == 7e-6 and loaded.source == "cached"
    finally:
        pm.reset_calibration()
