"""The serving engine's spans on the profiler's clock: every span name with
its stats, one request's spans agreeing with its own times, no nesting on
a thread, and the program names the benchmark's trace readers look for."""

import dataclasses
import glob
import os
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core.plan import single_device_plan
from repro.models.lm import LM
from repro.serving import InferenceEngine, Request
from repro.serving import engine as engine_mod

pytestmark = pytest.mark.serving

STATS = {"serve.prefill": {"req", "len", "queued_us"},
         "serve.insert": {"req", "slot", "ready_us"},
         "serve.decode": {"step", "batch"},
         "serve.sync": {"step"},
         "serve.collect": {"step", "finished"}}


class _Stamped(list):
    """A token list that stamps the time each token is appended."""

    def __init__(self, items=()):
        super().__init__(items)
        self.times = [time.perf_counter()] * len(self)

    def append(self, x):
        super().append(x)
        self.times.append(time.perf_counter())


@dataclasses.dataclass
class _TimedRequest(Request):
    def __setattr__(self, name, value):
        if name == "tokens" and value is not None:
            value = _Stamped(value)
        object.__setattr__(self, name, value)


@pytest.fixture(scope="module")
def served():
    cfg = get("ff-tiny").reduced()
    params = LM(cfg).init(jax.random.PRNGKey(0))
    return cfg, single_device_plan(), params


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """Five requests through the engine under a profiler session: the
    requests and the ``serve.*`` spans read back, as
    ``(thread, start_ns, dur_ns, name, stats)``."""
    cfg, plan, params = served
    rng = np.random.default_rng(0)
    reqs = [_TimedRequest(prompt=rng.integers(0, cfg.vocab, n,
                                              dtype=np.int32),
                          max_new_tokens=m)
            for n, m in [(8, 4), (12, 3), (8, 5), (16, 2), (12, 4)]]
    eng = InferenceEngine(cfg, plan, params, max_batch=2, cache_len=64)
    with eng:                            # compile outside the trace
        eng.submit(Request(prompt=reqs[0].prompt, max_new_tokens=2)) \
            .result(timeout=300)
        d = str(tmp_path_factory.mktemp("profile"))
        jax.profiler.start_trace(d)
        try:
            handles = [eng.submit(r) for r in reqs]
            for h in handles:
                h.result(timeout=300)
        finally:
            jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            spans.extend((i, e.start_ns, e.duration_ns, e.name,
                          dict(e.stats))
                         for e in line.events if e.name.startswith("serve."))
    return reqs, spans


def test_every_span_is_written_with_its_stats(traced):
    _, spans = traced
    names = {engine_mod.SPAN_PREFILL, engine_mod.SPAN_INSERT,
             engine_mod.SPAN_DECODE, engine_mod.SPAN_SYNC,
             engine_mod.SPAN_COLLECT}
    assert names == set(STATS)
    assert {n for _, _, _, n, _ in spans} == names
    for _, _, dur, name, stats in spans:
        assert set(stats) == STATS[name], name
        assert dur >= 0


def test_spans_of_a_request_agree_with_its_times(traced):
    reqs, spans = traced
    ids = {r.id for r in reqs}
    by_req = defaultdict(dict)
    for _, _, _, name, st in spans:
        if "req" in st:
            assert st["req"] in ids
            by_req[st["req"]][name] = st
    assert set(by_req) == ids
    for r in reqs:
        pre = by_req[r.id]["serve.prefill"]
        ins = by_req[r.id]["serve.insert"]
        assert pre["len"] == len(r.prompt)
        assert pre["queued_us"] >= 0 and ins["ready_us"] >= 0
        first_us = (r.tokens.times[0] - r.submit_t) * 1e6
        assert first_us >= pre["queued_us"] + ins["ready_us"]
    finished = sum(st["finished"] for _, _, _, n, st in spans
                   if n == "serve.collect")
    assert finished == len(reqs)


def test_one_tick_shares_its_step(traced):
    _, spans = traced
    steps = defaultdict(set)
    for _, _, _, name, st in spans:
        if "step" in st:
            steps[name].add(st["step"])
    assert steps["serve.decode"] == steps["serve.sync"] \
        == steps["serve.collect"]
    assert all(st["batch"] >= 1 for _, _, _, n, st in spans
               if n == "serve.decode")


def test_spans_on_one_thread_never_nest(traced):
    _, spans = traced
    by_thread, threads = defaultdict(list), defaultdict(set)
    for thread, start, dur, name, _ in spans:
        by_thread[thread].append((start, start + dur))
        threads[name].add(thread)
    # each node writes on its own thread; the collector syncs and collects
    tick = [threads[n] for n in ("serve.insert", "serve.decode",
                                 "serve.collect")]
    assert all(len(t) == 1 for t in tick)
    assert len(set.union(*tick)) == 3
    assert threads["serve.sync"] == threads["serve.collect"]
    assert not threads["serve.prefill"] & set.union(*tick)
    for ivs in by_thread.values():
        ivs.sort()
        for (_, end), (start, _) in zip(ivs, ivs[1:]):
            assert start >= end


def test_steps_lower_under_the_names_trace_readers_look_for(served):
    cfg, plan, params = served
    eng = InferenceEngine(cfg, plan, params, max_batch=2, cache_len=64)
    st = eng.state
    decode = eng._decode_node._decode.lower(
        params, st.caches, {"token": st.cur_tok, "pos": st.pos})
    prefill = eng._prefill_node._prefill.lower(
        params, jnp.zeros((1, 8), jnp.int32))
    assert "module @jit__decode " in decode.as_text()
    assert "module @jit__prefill " in prefill.as_text()
