"""Adaptive runtime: live re-placement (width + tier), the supervisor's
stats -> placement loop, and the online cost-model refinement.

Covers the reconfiguration invariants:
- supervisor-disabled (adaptive=False) runs behave exactly like before;
- a mid-stream tier migration preserves exact input order on streams longer
  than the ring capacity;
- a worker crash during a drain/swap surfaces WorkerCrashed instead of
  wedging;
- ``perf_model.observe()`` measurably shifts a subsequent compile()'s
  placement.

The end-to-end GIL-flip test turns a farm's workload GIL-bound mid-stream
and asserts that the supervisor migrates it from threads to processes live
and that the output keeps its exact order.  It times nothing: a CPU timing
is no evidence of speed.
"""

import json
import os
import signal
import threading
import time

import pytest

from repro.core import (EOS, GraphError, ProcessRunner, Supervisor,
                        WorkerCrashed, farm, pipeline, seq)
from repro.core import perf_model as pm
from repro.core.compiler import annotate, place, _top_stages
from repro.core.node import FFNode

pytestmark = pytest.mark.runtime


@pytest.fixture(autouse=True)
def _isolated_calibration(tmp_path, monkeypatch):
    """Every test here sees a private calibration/observed cache: the
    supervisor's observe() must never leak test workloads into the real
    cache (or into other tests' placement decisions)."""
    monkeypatch.setenv("REPRO_FF_CALIB_CACHE",
                       str(tmp_path / "calibration.json"))
    pm.reset_calibration()
    pm.reset_observed()
    yield
    pm.reset_calibration()
    pm.reset_observed()


def _write_fake_calibration():
    """Pre-seed the (isolated) cache so place() never pays a measurement."""
    path = os.environ["REPRO_FF_CALIB_CACHE"]
    with open(path, "w") as f:
        json.dump({"version": 1, "cpu_count": os.cpu_count(),
                   "peak_flops": 5e10, "queue_hop_s": 2e-5,
                   "proc_hop_s": 1e-4, "device_dispatch_s": 2e-5}, f)
    pm.reset_calibration()


class _Gen(FFNode):
    def __init__(self, n):
        super().__init__()
        self.i, self.n = 0, n

    def svc(self, _):
        self.i += 1
        return float(self.i) if self.i <= self.n else None


def _double(x):
    return x * 2.0


def _flip_worker(x):
    """GIL-releasing (sleep) until the flip file appears, then GIL-bound
    pure-Python compute.  Output is phase-independent so order/content
    checks stay exact.  Worker processes forked after the flip inherit the
    env var and see the file too."""
    if os.path.exists(os.environ.get("REPRO_FF_TEST_FLIP", "/nonexistent")):
        s = 0.0
        for i in range(100000):
            s += (x * i) % 7.3
    else:
        time.sleep(0.004)
    return x * 2.0


def _sleepy(x):
    time.sleep(0.002)
    return x + 1.0


# ---------------------------------------------------------------------------
# adaptive=False is byte-identical to the static path
# ---------------------------------------------------------------------------
def test_supervisor_disabled_is_static_behavior():
    def build():
        return pipeline(_Gen(64), farm(_double, n=2))

    r_static = build().compile(mode="host")
    out_static = r_static.run()
    # no adaptive machinery anywhere in the static runner
    assert not any(getattr(st, "ff_adaptive", False)
                   for st in r_static._top_members())
    assert all(not h.reconfigurable for h in r_static.stage_handles())
    assert r_static.replacement_events() == []

    r_adaptive = build().compile(mode="host", adaptive=True)
    assert any(getattr(st, "ff_adaptive", False)
               for st in r_adaptive._top_members())
    out_adaptive = r_adaptive.run()
    # identical output values; the adaptive farm is sequence-ordered, which
    # for this 1->1 stage means identical order too
    assert out_adaptive == sorted(out_static) == \
        [2.0 * i for i in range(1, 65)]
    # with no supervisor attached, nothing was re-placed
    assert r_adaptive.replacement_events() == []


def test_adaptive_placement_report_marks_stage():
    r = pipeline(_Gen(4), farm(_double, n=2)).compile(mode="host",
                                                      adaptive=True)
    targets = {desc: p for desc, p in r.placements}
    farm_p = next(p for d, p in targets.items() if "farm" in d)
    assert "adaptive" in farm_p.reason
    r.run()


# ---------------------------------------------------------------------------
# the uniform StageHandle surface
# ---------------------------------------------------------------------------
def test_stage_handles_uniform_across_runners(plan):
    # host threads
    r = pipeline(_Gen(8), farm(_double, n=2)).compile(mode="host")
    r.run()
    hs = r.stage_handles()
    assert len(hs) == 2 and all(isinstance(h.stats(), dict) for h in hs)
    # process tier
    rp = pipeline(_Gen(8), farm(_double, n=2)).compile(mode="process")
    assert isinstance(rp, ProcessRunner)
    rp.run()
    hp = rp.stage_handles()
    assert any(h.stats().get("backend") == "process" for h in hp)
    # device tier: the fused segment is ONE entry whose label lists the
    # composed stages; fuse=False restores the per-stage split
    rd = pipeline(seq(lambda x: x + 1.0, pure=True),
                  seq(lambda x: x * 2.0, pure=True)).compile(
        plan, mode="device")
    out = rd.run([1.0, 2.0, 3.0])
    assert [float(y) for y in out] == [4.0, 6.0, 8.0]
    st = rd.stats()
    assert len(st["stages"]) == 1
    assert " + " in st["stages"][0]["node"]
    assert all(s["items"] == 3 for s in st["stages"])
    hd = rd.stage_handles()
    assert len(hd) == 1
    assert all(h.stats()["backend"] == "device" for h in hd)
    assert all(not h.reconfigurable for h in hd)
    rs = pipeline(seq(lambda x: x + 1.0, pure=True),
                  seq(lambda x: x * 2.0, pure=True)).compile(
        plan, mode="device", fuse=False)
    rs.run([1.0, 2.0, 3.0])
    assert len(rs.stats()["stages"]) == 2
    assert len(rs.stage_handles()) == 2


def test_non_reconfigurable_handle_refuses():
    r = pipeline(_Gen(4), farm(_double, n=2)).compile(mode="host")
    h = r.stage_handles()[0]
    with pytest.raises(GraphError):
        h.resize(2)
    with pytest.raises(GraphError):
        h.migrate("host_process")
    r.run()


# ---------------------------------------------------------------------------
# migration preserves exact order on streams longer than the ring capacity
# ---------------------------------------------------------------------------
@pytest.mark.shm
def test_migration_preserves_order_beyond_ring_capacity():
    N = 400                              # engine lanes are <= 8 slots deep

    def work(x):
        time.sleep(0.001)                # keep the stream alive across swaps
        return x * 2.0

    g = farm(work, n=2)
    r = g.compile(mode="host", adaptive=True, capacity=16)
    r.run_then_freeze()
    h = r.stage_handles()[0]
    got = []
    done = threading.Event()

    def collect():
        while True:
            ok, item = r.load_result(timeout=60.0)
            if not ok:
                break
            got.append(item)
        done.set()

    threading.Thread(target=collect, daemon=True).start()

    def feed():
        for i in range(N):
            r.offload(float(i))
        r.offload(EOS)

    threading.Thread(target=feed, daemon=True).start()
    time.sleep(0.02)
    assert h.migrate("host_process") is True     # mid-stream swap out ...
    time.sleep(0.05)
    h.migrate("host")                            # ... and back
    assert done.wait(120.0)
    assert r.wait(30.0) == 0
    assert got == [2.0 * i for i in range(N)]
    assert len(r.replacement_events()) >= 1


# ---------------------------------------------------------------------------
# crash during a drain/swap surfaces WorkerCrashed instead of wedging
# ---------------------------------------------------------------------------
@pytest.mark.shm
def test_worker_crash_during_drain_swap_surfaces_error():
    g = farm(_sleepy, n=2)
    r = g.compile(mode="process", adaptive=True)
    r.run_then_freeze()
    h = r.stage_handles()[0]
    assert h.tier == "host_process"
    node = h.node
    for i in range(4):
        r.offload(float(i))
    time.sleep(0.3)
    for p in node._engine._procs:                # crash both workers
        os.kill(p.pid, signal.SIGKILL)
    with pytest.raises(WorkerCrashed):
        h.migrate("host")                        # drain hits the crash
    # the runner unwinds instead of wedging, and the error is preserved
    assert r.wait(30.0) == -1
    assert isinstance(r.error(), WorkerCrashed)


# ---------------------------------------------------------------------------
# supervisor width policy: AutoscaleLB generalized
# ---------------------------------------------------------------------------
def test_supervisor_resizes_active_workers_from_lane_depth():
    g = farm(_sleepy, n=2)
    r = g.compile(mode="host", adaptive=True)
    r.run_then_freeze()
    sup = Supervisor(r, interval=0.01, migrate=False).start()
    got = []
    done = threading.Event()

    def collect():
        while True:
            ok, item = r.load_result(timeout=60.0)
            if not ok:
                break
            got.append(item)
        done.set()

    threading.Thread(target=collect, daemon=True).start()
    # trickle: lanes stay empty -> the supervisor retires a worker
    for i in range(12):
        r.offload(float(i))
        time.sleep(0.02)
    deadline = time.monotonic() + 10.0
    while not any(e.kind == "shrink" for e in sup.events) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    # burst: deep lanes -> the supervisor reactivates it
    for i in range(12, 120):
        r.offload(float(i))
    r.offload(EOS)
    assert done.wait(60.0)
    assert r.wait(30.0) == 0
    sup.stop()
    kinds = {e.kind for e in sup.events}
    assert "shrink" in kinds
    assert "grow" in kinds
    assert got == [i + 1.0 for i in range(120)]  # seq-ordered throughout


# ---------------------------------------------------------------------------
# the acceptance loop: GIL flip mid-stream -> thread->process migration
# ---------------------------------------------------------------------------
@pytest.mark.shm
@pytest.mark.slow
def test_gil_flip_migrates_live_and_keeps_order(tmp_path, monkeypatch):
    flip = tmp_path / "flip"
    monkeypatch.setenv("REPRO_FF_TEST_FLIP", str(flip))
    n1, n2 = 16, 200

    def run_stream(supervised: bool):
        if flip.exists():
            flip.unlink()
        g = farm(_flip_worker, n=2)
        r = g.compile(mode="host", adaptive=True)
        r.run_then_freeze()
        sup = Supervisor(r, interval=0.02) if supervised else None
        if sup:
            sup.start()
        got = []
        done = threading.Event()

        def collect():
            while True:
                ok, item = r.load_result(timeout=120.0)
                if not ok:
                    break
                got.append(item)
            done.set()

        threading.Thread(target=collect, daemon=True).start()
        for i in range(n1):
            r.offload(float(i))
        time.sleep(0.1)
        flip.touch()                     # the workload turns GIL-bound
        for i in range(n1, n1 + n2):
            r.offload(float(i))
        r.offload(EOS)
        assert done.wait(300.0)
        assert r.wait(60.0) == 0
        if sup:
            sup.stop()
        return got, (list(sup.events) if sup else [])

    got_sup, events = run_stream(True)
    got_ctl, _ = run_stream(False)
    expected = [2.0 * i for i in range(n1 + n2)]

    # 1. the migration happened, thread -> process, while the stream ran
    migrations = [e for e in events if e.kind == "migrate"]
    assert any("host_process" in e.detail for e in migrations), events
    # 2. exact output order preserved across the swap (and in the control)
    assert got_sup == expected
    assert got_ctl == expected


# ---------------------------------------------------------------------------
# online cost-model refinement: observe() shifts the next compile
# ---------------------------------------------------------------------------
def _observed_worker(x):
    return x


def test_observe_shifts_subsequent_placement():
    _write_fake_calibration()
    key = pm.fn_key(_observed_worker)

    # before any history: no cost info, the farm stays on threads
    g0 = pipeline(_Gen(4), farm(_observed_worker, n=4)).optimize()
    annotate(g0)
    place(g0)
    farm_stage = _top_stages(g0)[1]
    assert farm_stage.placement.target == "host"
    assert farm_stage.cost.source == "default"

    # a runtime observation: 4ms/item of CPU, demonstrably GIL-serialized
    absorbed = pm.observe({"stages": [{
        "backend": "thread", "fn_key": key, "items": 64, "delivered": 64,
        "svc_cpu_ema_s": 4e-3, "svc_wall_ema_s": 8e-3,
        "gil_ratio": 0.5, "active": 2}]}, write=True)
    assert absorbed == 1
    rec = pm.lookup_observed(key)
    assert rec is not None and rec["releases_gil"] is False

    # the next compile's annotate/place consumes the history: same graph,
    # still no costs=/sample=, now lands on the process tier
    g1 = pipeline(_Gen(4), farm(_observed_worker, n=4)).optimize()
    annotate(g1)
    place(g1)
    farm_stage = _top_stages(g1)[1]
    assert farm_stage.cost.source == "observed"
    assert farm_stage.cost.releases_gil is False
    assert farm_stage.placement.target == "host_process", \
        farm_stage.placement

    # the observed table persists: a fresh in-memory state reloads it
    pm.reset_observed()
    assert pm.lookup_observed(key) is not None


def test_observe_refines_proc_hop_calibration():
    _write_fake_calibration()
    before = pm.get_calibration(measure=False).proc_hop_s
    absorbed = pm.observe({"stages": [{
        "backend": "process", "items": 64, "hop_ema_s": 9e-4}]})
    assert absorbed == 1
    after = pm.get_calibration(measure=False)
    assert after.source == "observed"
    assert before < after.proc_hop_s < 9e-4   # EMA moved toward the sample


def test_observe_ignores_thin_or_foreign_records():
    assert pm.observe({"stages": [
        {"backend": "thread", "fn_key": "x.y", "items": 2,
         "svc_cpu_ema_s": 1e-3},                  # too few items
        {"backend": "process", "items": 64},      # no hop measured
        {"unrelated": True},
    ]}) == 0


# ---------------------------------------------------------------------------
# stats() is safe and consistent mid-stream
# ---------------------------------------------------------------------------
def test_stats_consistent_midstream():
    g = pipeline(_Gen(300), farm(_sleepy, n=2))
    r = g.compile(mode="host", adaptive=True)
    errors = []
    stop = threading.Event()

    def hammer():
        handles = r.stage_handles()
        while not stop.is_set():
            try:
                for h in handles:
                    s = h.stats()
                    if "delivered" in s:
                        assert s["delivered"] <= s["items"]
                r.stats()
            except Exception as e:       # noqa: BLE001
                errors.append(e)
                return

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    out = r.run(timeout=120.0)
    stop.set()
    t.join(10.0)
    assert not errors
    assert out == [i + 1.0 for i in range(1, 301)]
