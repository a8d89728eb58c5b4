"""Staged graph compiler (normalize -> annotate -> place -> emit): cost-model
sanity, cost-driven hybrid placement, device lowerings for all_to_all (MoE
dispatch/combine) and wrap_around (feedback_scan) with host parity, farm
width selection, and autoscaling host farms."""

import time

import numpy as np
import pytest

from repro.core import (Deliver, FF_EOS, FFNode, GO_ON, GraphError,
                        all_to_all, farm, pipeline)
from repro.core import perf_model as pm
from repro.core.compiler import (CostEstimate, HybridRunner, Placement,
                                 annotate, place)
from repro.core.graph import FarmG
from repro.core.skeletons import AutoscaleLB


class Gen(FFNode):
    def __init__(self, n):
        super().__init__()
        self.i, self.n = 0, n

    def svc(self, _):
        self.i += 1
        return np.float32(self.i) if self.i <= self.n else None


# -- annotate: the cost model ---------------------------------------------------
def test_annotate_measures_and_reads_declarations():
    def slow(x):
        time.sleep(0.002)
        return x

    def declared(x):
        return x
    declared.ff_cost = 0.5
    declared.ff_flops = 1e9

    g = pipeline(slow, farm(declared, n=2)).optimize()
    annotate(g, sample=np.float32(1.0))
    s_slow, s_farm = g.root.stages
    assert s_slow.cost.source == "measured"
    assert 0.0015 < s_slow.cost.t_task < 0.05
    assert s_farm.cost.source == "declared"
    assert s_farm.cost.t_task == 0.5 and s_farm.cost.flops == 1e9


def test_annotate_estimate_matches_measured_farm_time():
    """The paper's Sec. 13 algebra, fed by annotate's measured t_task, must
    predict the HostRunner farm completion time within a loose factor
    (sleep releases the GIL, so workers genuinely overlap)."""
    def slow(x):
        time.sleep(0.002)
        return x

    m, nw = 24, 4
    g = farm(slow, n=nw).optimize()
    annotate(g, sample=np.float32(0.0))
    t_task = g.root.cost.t_task
    predicted = pm.farm_time(m, t_task, nw)

    t0 = time.perf_counter()
    out = farm(slow, n=nw).lower().run([np.float32(i) for i in range(m)])
    measured = time.perf_counter() - t0
    assert len(out) == m
    assert predicted / 5 < measured < predicted * 5, (predicted, measured)


def test_costs_dict_overrides_declarations():
    fn = lambda x: x
    g = farm(fn, n=2).optimize()
    annotate(g, costs={fn: 0.125})
    assert g.root.cost.t_task == 0.125 and g.root.cost.source == "given"


# -- place: cost-driven placement and width selection --------------------------
def test_place_chooses_farm_width_from_cost_model(plan):
    g = farm(lambda x: x, n="auto").optimize()
    g.root.cost = CostEstimate(t_task=1e-4, source="given")
    place(g, plan)
    p = g.root.placement
    assert p.target == "host"
    # the channel floor is the calibration place() used: the baked-in
    # default, or a cache another test on this host has written
    hop = pm.get_calibration(measure=False).queue_hop_s
    assert p.width == pm.choose_farm_width(1e-4, __import__("os").cpu_count(),
                                           overhead=hop)
    assert 1 <= p.width <= (__import__("os").cpu_count() or 1)


def test_place_prefers_device_for_declared_flops(plan):
    heavy = lambda x: x * 2.0
    heavy.ff_flops = 1e9
    g = pipeline(Gen(4), farm(heavy, n=2)).optimize()
    annotate(g)
    place(g, plan)
    src, f = g.root.stages
    assert src.placement.target == "host"       # stateful: host-only
    assert f.placement.target == "device"
    assert "roofline" in f.placement.reason


def test_place_overrides_pin_stages(plan):
    heavy = lambda x: x * 2.0
    heavy.ff_flops = 1e9
    g = pipeline(Gen(4), farm(heavy, n=2)).optimize()
    annotate(g)
    place(g, plan, overrides={1: "host"})
    assert g.root.stages[1].placement.target == "host"
    place(g, plan, overrides={heavy: Placement("host", width=2)})
    assert g.root.stages[1].placement.target == "host"
    assert g.root.stages[1].placement.width == 2


# -- emit: the hybrid runner (acceptance criterion) ----------------------------
def test_hybrid_compile_mixes_host_and_device_stages(plan):
    heavy = lambda x: x * 2.0 + 1.0
    heavy.ff_flops = 1e9

    n = 13                                    # not a multiple of the batch
    r = pipeline(Gen(n), farm(heavy, n=2)).compile(plan, device_batch=4)
    assert isinstance(r, HybridRunner)
    targets = [p.target for _, p in r.placements]
    assert "host" in targets and "device" in targets
    out = sorted(float(v) for v in r.run())
    assert out == [i * 2.0 + 1.0 for i in range(1, n + 1)]
    assert r.describe_placements()


def test_hybrid_parity_with_all_host(plan):
    heavy = lambda x: x * 3.0 - 1.0
    heavy.ff_flops = 1e9

    def build():
        return pipeline(Gen(10), farm(heavy, n=2), lambda x: x + 0.5)

    hybrid = sorted(float(v) for v in build().compile(plan).run())
    host = sorted(float(v) for v in build().compile(plan, mode="host").run())
    assert hybrid == host == [i * 3.0 - 0.5 for i in range(1, 11)]


def test_device_stage_error_is_reported_not_hung(plan):
    bad = lambda x: x @ x                     # 0-d matmul: traces then dies
    bad.ff_flops = 1e9
    r = pipeline(Gen(3), farm(bad, n=2)).compile(plan, device_batch=2)
    assert [p.target for _, p in r.placements][1] == "device"
    with pytest.raises(BaseException):
        r.run()


# -- device all_to_all: MoE-style dispatch/combine -----------------------------
def test_a2a_device_parity_default_router(plan):
    lefts = [lambda x: x * 10.0, lambda x: x + 1.0]
    rights = [lambda y: y - 1.0, lambda y: y * 2.0, lambda y: y + 3.0]
    xs = [np.float32(i) for i in range(12)]

    host = sorted(float(v) for v in
                  all_to_all(lefts, rights).compile(mode="host").run(xs))
    dev = sorted(float(v) for v in
                 all_to_all(lefts, rights).compile(plan, mode="device").run(xs))
    assert host == dev


def test_a2a_device_parity_custom_router(plan):
    import jax.numpy as jnp
    lefts = [lambda x: x * 2.0]
    rights = [lambda y: y + 100.0, lambda y: y - 100.0]
    router = lambda y, n: jnp.asarray(y, jnp.int32) % n   # traceable AND host-usable
    xs = [np.float32(i) for i in range(10)]

    host = sorted(float(v) for v in
                  all_to_all(lefts, rights, router).compile(mode="host").run(xs))
    dev = sorted(float(v) for v in
                 all_to_all(lefts, rights, router).compile(plan, mode="device").run(xs))
    assert host == dev


def test_a2a_in_pipeline_compiles_to_device(plan):
    rights = [lambda y: y * 2.0, lambda y: y + 7.0]
    xs = [np.float32(i) for i in range(8)]

    def build():
        return pipeline(lambda x: x + 1.0,
                        all_to_all([lambda x: x * 10.0], rights))
    host = sorted(float(v) for v in build().compile(mode="host").run(xs))
    dev = sorted(float(v) for v in
                 build().compile(plan, mode="device").run(xs))
    assert host == dev


def test_a2a_device_rejects_stateful_workers(plan):
    class St(FFNode):
        def svc(self, t):
            return t

    with pytest.raises(GraphError):
        all_to_all([St()], [lambda x: x]).compile(plan, mode="device")


# -- device wrap_around: feedback_scan -----------------------------------------
def test_feedback_device_parity_with_host_loop(plan):
    K = 4

    def f(x):
        return x * 0.5 + 1.0

    class KLoop(FFNode):
        """Host comparator: each item circles the feedback edge K times,
        then escapes via Deliver; terminates once the drain marker arrives
        and nothing is in flight."""

        def __init__(self):
            super().__init__()
            self.inflight = 0
            self.draining = False

        def svc(self, t):
            if t == "drain":
                self.draining = True
            else:
                if isinstance(t, tuple):
                    self.inflight -= 1
                    x, k = t
                else:
                    x, k = t, 0
                x, k = f(x), k + 1
                if k < K:
                    self.inflight += 1
                    self.ff_send_out((x, k))
                else:
                    self.ff_send_out(Deliver(x))
            if self.draining and self.inflight == 0:
                return None
            return GO_ON

    xs = [np.float32(8.0), np.float32(16.0), np.float32(-4.0)]
    r = pipeline(KLoop()).wrap_around().lower()
    r.run_then_freeze()
    for x in xs:
        r.offload(x)
    r.offload("drain")
    host = []
    while True:
        ok, v = r.load_result(timeout=30)
        if not ok:
            break
        host.append(float(v))
    assert r.wait(timeout=30) == 0

    dev_r = pipeline(f).wrap_around().compile(plan, feedback_steps=K)
    assert all(p.target == "device" for _, p in dev_r.placements)
    dev = [float(v) for v in dev_r.run(xs)]
    assert sorted(host) == pytest.approx(sorted(dev))


def test_feedback_device_needs_step_count(plan):
    # without feedback_steps the loop cannot lower to the mesh: auto mode
    # falls back to host; forced device mode refuses
    r = pipeline(lambda x: x).wrap_around().compile(plan)
    assert all(p.target == "host" for _, p in r.placements)
    with pytest.raises(GraphError):
        pipeline(lambda x: x).wrap_around().compile(plan, mode="device")


# -- autoscaling host farms ----------------------------------------------------
def test_autoscale_lb_grows_on_depth_and_shrinks_when_idle():
    from repro.core.queues import SPMCQueue
    lb = AutoscaleLB(max_workers=4, hi=1.0, lo=0.25, adjust_every=4)
    lanes = SPMCQueue(4, 64)
    lb._attach(lanes)
    assert lb.cur == 1
    for i in range(24):                     # nobody drains: depth builds up
        lb.route(i)
    assert lb.cur > 1 and lb.grown >= 1
    grown_to = lb.cur
    for lane in lanes.lanes:                # consumers catch up
        while lane.try_pop()[0]:
            pass
    for i in range(64):                     # keep lanes empty while routing
        lb.route(i)
        for lane in lanes.lanes:
            lane.try_pop()
    assert lb.shrunk >= 1 and lb.cur < grown_to


def test_autoscale_farm_end_to_end():
    g = farm(lambda x: x + 1, n=3, autoscale=True)
    assert isinstance(g.root, FarmG) and g.root.autoscale
    r = g.lower(capacity=8)
    out = sorted(r.run(range(40)))
    assert out == list(range(1, 41))


def test_autoscale_farm_defaults_to_cpu_count_bound():
    import os
    g = farm(lambda x: x * 2, autoscale=True)     # n omitted -> n_auto
    assert g.root.n_auto
    r = g.lower(capacity=8)
    skel = r._skel
    # lowered as a Farm of cpu_count parked workers behind an AutoscaleLB
    from repro.core.skeletons import Farm as HostFarm
    f = skel._stages[0] if not isinstance(skel, HostFarm) else skel
    assert isinstance(f.getlb(), AutoscaleLB)
    assert len(f._workers) == max(1, os.cpu_count() or 1)
    out = sorted(r.run(range(20)))
    assert out == [x * 2 for x in range(20)]


def test_autoscale_rejects_stateful_and_custom_lb():
    from repro.core import BroadcastLB

    class St(FFNode):
        def svc(self, t):
            return t

    with pytest.raises(GraphError):
        farm([St()], autoscale=True)
    with pytest.raises(GraphError):
        farm(lambda x: x, n=2, autoscale=True, lb=BroadcastLB())
    with pytest.raises(GraphError):
        farm(lambda x: x, n=2, autoscale=True, ondemand=1)


def test_bad_placement_target_rejected(plan):
    with pytest.raises(GraphError):
        pipeline(Gen(2), lambda x: x).compile(
            plan, placements={0: Placement(target="tpu")})


def test_autoscale_farm_stays_host_even_with_flops(plan):
    heavy = lambda x: x * 2.0
    heavy.ff_flops = 1e9
    r = pipeline(Gen(4), farm(heavy, n=2, autoscale=True)).compile(plan)
    p = dict(r.placements)[
        [d for d, _ in r.placements if "farm" in d][0]]
    assert p.target == "host" and "autoscale" in p.reason
    assert sorted(float(v) for v in r.run()) == [i * 2.0 for i in range(1, 5)]


def test_device_mode_without_plan_is_a_graph_error():
    with pytest.raises(GraphError):
        pipeline(lambda x: x).compile(mode="device")


def test_a2a_capacity_factor_bounds_lanes(plan):
    import jax.numpy as jnp
    # everything routes to expert 0; a tight capacity drops the overflow
    # (T=32, nR=2, factor=0.5 -> expert_capacity=8 slots < 32 arrivals)
    T = 32
    router = lambda y, n: jnp.int32(0)
    xs = [np.float32(i + 1) for i in range(T)]
    lossless = all_to_all([lambda x: x], [lambda y: y * 2.0, lambda y: y],
                          router=router).compile(plan, mode="device").run(xs)
    assert sorted(float(v) for v in lossless) == \
        [2.0 * (i + 1) for i in range(T)]
    bounded = all_to_all([lambda x: x], [lambda y: y * 2.0, lambda y: y],
                         router=router).compile(
        plan, mode="device", a2a_capacity_factor=0.5).run(xs)
    kept = [float(v) for v in bounded if float(v) != 0.0]
    assert len(bounded) == T and 0 < len(kept) < T    # overflow -> zeros
    assert kept == [2.0 * (i + 1) for i in range(len(kept))]  # FCFS lanes


def test_fusion_never_drops_auto_width():
    class St(FFNode):
        def svc(self, t):
            return t

    # auto farm followed by an explicit single-worker farm: the composed fn
    # is unavailable, so fusion must be skipped rather than pin width to 1
    g = pipeline(farm(lambda x: x + 1, n="auto"),
                 farm([lambda x: x * 2])).optimize()
    stages = g.root.stages
    assert len(stages) == 2 and stages[0].n_auto
    # two auto farms DO fuse, and the fused farm stays auto
    g2 = pipeline(farm(lambda x: x + 1, n="auto"),
                  farm(lambda x: x * 2, n="auto")).optimize()
    assert isinstance(g2.root, FarmG) and g2.root.n_auto
    assert sorted(g2.root.fn(x) for x in range(5)) == \
        [(x + 1) * 2 for x in range(5)]


# -- a2a hardening: dead left worker never wedges the producer -----------------
def test_a2a_crashed_left_worker_releases_producer():
    def boom(t):
        raise RuntimeError("left worker down")

    g = all_to_all([boom, lambda x: x * 2], [lambda x: x])
    r = g.lower(capacity=4)
    r.run_then_freeze()
    for i in range(60):                     # far beyond every lane capacity
        r.offload(i)
    r.offload(FF_EOS)
    got = []
    while True:
        ok, v = r.load_result(timeout=30)
        if not ok:
            break
        got.append(v)
    assert r.wait(timeout=30) == -1
    assert isinstance(r.error(), RuntimeError)
    assert got == [i * 2 for i in range(1, 60, 2)]   # surviving left worker


# -- data-dependent feedback: feedback_cond / feedback_while --------------------
def test_feedback_cond_host_device_parity(plan):
    """The same ``feedback_cond=`` predicate drives the host re-entry path
    and the device ``feedback_while`` lowering to identical values; host
    wrap output is arrival-ordered, so compare sorted."""

    def f(x):
        return x * np.float32(0.5)

    def still_big(x):
        return x > 1.0

    xs = [np.float32(5.0), np.float32(1.5), np.float32(40.0)]

    host_r = pipeline(f).wrap_around().compile(feedback_cond=still_big)
    host = [float(v) for v in host_r.run(xs)]

    dev_r = pipeline(f).wrap_around().compile(
        plan, feedback_cond=still_big, feedback_steps=64)
    assert all(p.target == "device" for _, p in dev_r.placements)
    dev = [float(v) for v in dev_r.run(xs)]

    assert sorted(host) == pytest.approx(sorted(dev))
    # the exit was data-dependent, not the 64-step cap: every lane stopped
    # as soon as it crossed 1.0 (running to the cap would leave ~1e-18)
    assert all(0.5 < v <= 1.0 for v in dev)


def test_feedback_cond_alone_lowers_to_device(plan):
    # a data-dependent predicate needs no step bound to reach the mesh
    r = pipeline(lambda x: x * np.float32(0.25)).wrap_around().compile(
        plan, feedback_cond=lambda x: x > 1.0)
    assert all(p.target == "device" for _, p in r.placements)
    out = sorted(float(v) for v in r.run([np.float32(8.0),
                                          np.float32(2.0)]))
    assert out == pytest.approx([0.5, 0.5])


def test_feedback_while_counts_steps_and_respects_cap():
    import jax.numpy as jnp
    from repro.core.device import feedback_while

    step = lambda s: (s * 0.5, 0.0)
    final, n = feedback_while(step, jnp.float32(8.0), lambda s: s > 1.0)
    assert float(final) == pytest.approx(1.0) and int(n) == 3
    # do-while: the body always runs at least once
    final, n = feedback_while(step, jnp.float32(0.25), lambda s: s > 1.0)
    assert float(final) == pytest.approx(0.125) and int(n) == 1
    # the cap wins when the predicate would keep going
    final, n = feedback_while(step, jnp.float32(1e9), lambda s: s > 1.0,
                              max_steps=3)
    assert int(n) == 3


# -- CompileConfig: the consolidated compile surface ----------------------------
def test_compile_config_equivalent_to_legacy_kwargs():
    from repro.core import CompileConfig
    xs = [np.float32(i) for i in range(6)]

    def tw(x):
        return x * np.float32(2.0)

    with pytest.warns(DeprecationWarning) as rec:
        old = pipeline(tw).compile(capacity=8).run(xs)
    assert len([w for w in rec if w.category is DeprecationWarning]) == 1

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        new = pipeline(tw).compile(
            config=CompileConfig(capacity=8)).run(xs)
        bare = pipeline(tw).compile().run(xs)  # no kwargs: no warning
    assert [float(v) for v in old] == [float(v) for v in new]
    assert [float(v) for v in bare] == [float(v) for v in new]


def test_compile_config_rejects_mixing_and_unknown_knobs():
    from repro.core import CompileConfig
    g = pipeline(lambda x: x)
    with pytest.raises(TypeError):
        g.compile(capcity=8)  # typo'd knob: loud, not silently ignored
    with pytest.raises(GraphError):
        g.compile(config=CompileConfig(), capacity=8)
    with pytest.raises(GraphError):
        g.compile("not-none-plan", config=CompileConfig())
