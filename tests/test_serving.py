"""Serving engine: accelerator-mode API, continuous batching, greedy-decode
equivalence with a manual loop."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import FF_EOS
from repro.models.lm import LM
from repro.runtime.steps import make_decode_step, make_prefill_step
from repro.serving import InferenceEngine, Overloaded, Request

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def served(plan_module=None):
    from repro.core.plan import single_device_plan
    plan = single_device_plan()
    cfg = get("ff-tiny").reduced()
    params = LM(cfg).init(jax.random.PRNGKey(0))
    return cfg, plan, params


def _manual_greedy(cfg, plan, params, prompt, n_new, cache_len=64):
    prefill = jax.jit(make_prefill_step(cfg, plan, cache_len))
    decode = jax.jit(make_decode_step(cfg, plan, cache_len))
    logits, caches = prefill(params, {"tokens": prompt[None]})
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    pos = prompt.shape[0]
    for i in range(n_new - 1):
        tok, _, caches = decode(params, caches,
                                {"token": tok,
                                 "pos": jnp.asarray(pos + i, jnp.int32)})
        out.append(int(tok[0, 0]))
    return out


def test_engine_generates_and_matches_manual_loop(served):
    cfg, plan, params = served
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 8, dtype=np.int32)
    want = _manual_greedy(cfg, plan, params, jnp.asarray(prompt), 6)

    eng = InferenceEngine(cfg, plan, params, max_batch=2, cache_len=64)
    eng.run_then_freeze()
    eng.offload(Request(prompt=prompt, max_new_tokens=6, id=0))
    eng.offload(FF_EOS)
    ok, req = eng.load_result()
    assert ok and req.done
    assert eng.wait() == 0
    assert req.tokens == want


def test_engine_continuous_batching_many_requests(served):
    cfg, plan, params = served
    rng = np.random.default_rng(1)
    eng = InferenceEngine(cfg, plan, params, max_batch=3, cache_len=64)
    eng.run_then_freeze()
    N = 7
    for i in range(N):
        eng.offload(Request(prompt=rng.integers(0, cfg.vocab, 8,
                                                dtype=np.int32),
                            max_new_tokens=4 + (i % 3), id=i))
    eng.offload(FF_EOS)
    done = []
    while True:
        ok, req = eng.load_result()
        if not ok:
            break
        done.append(req)
    assert eng.wait() == 0
    assert sorted(r.id for r in done) == list(range(N))
    for r in done:
        assert len(r.tokens) == r.max_new_tokens
    # batched slots: fewer decode steps than sequential sum of lengths
    assert eng.steps < sum(r.max_new_tokens for r in done)


def test_engine_results_independent_of_batching(served):
    """Each request's tokens are the same whether served alone or packed
    with others (slot isolation)."""
    cfg, plan, params = served
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, 8, dtype=np.int32)
               for _ in range(3)]
    solo = [_manual_greedy(cfg, plan, params, jnp.asarray(p), 5)
            for p in prompts]
    eng = InferenceEngine(cfg, plan, params, max_batch=3, cache_len=64)
    eng.run_then_freeze()
    for i, p in enumerate(prompts):
        eng.offload(Request(prompt=p, max_new_tokens=5, id=i))
    eng.offload(FF_EOS)
    got = {}
    while True:
        ok, req = eng.load_result()
        if not ok:
            break
        got[req.id] = req.tokens
    eng.wait()
    for i in range(3):
        assert got[i] == solo[i], i


# -- typed client API ----------------------------------------------------------
def test_submit_handle_matches_compat_api(served):
    """submit()/result() produce the same greedy tokens as the paper's
    offload/load_result surface and the manual loop."""
    cfg, plan, params = served
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 8, dtype=np.int32)
    want = _manual_greedy(cfg, plan, params, jnp.asarray(prompt), 5)
    with InferenceEngine(cfg, plan, params, max_batch=2,
                         cache_len=64) as eng:
        h = eng.submit(Request(prompt=prompt, max_new_tokens=5))
        assert not h.done() or h.result(0) is not None
        out = h.result(timeout=120)
    assert isinstance(out, Request) and out.done
    assert out.finish_reason == "max_tokens"
    assert out.tokens == want


def test_results_iterator_and_context_manager(served):
    cfg, plan, params = served
    rng = np.random.default_rng(4)
    with InferenceEngine(cfg, plan, params, max_batch=2,
                         cache_len=64) as eng:
        ids = [eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=3)).request.id for _ in range(4)]
    # __exit__ drained the engine; results() replays every outcome
    got = {r.id: r for r in eng.results()}
    assert sorted(got) == sorted(ids)
    assert all(len(r.tokens) == 3 for r in got.values())
    # the iterator stays ended on re-iteration
    assert list(eng.results()) == []


def test_continuous_batching_refills_slots_from_ready_queue(served):
    """More requests than slots: the CacheManager refills freed slots
    mid-flight (continuous batching), so every request finishes and the
    cache sees as many inserts+evicts as requests."""
    cfg, plan, params = served
    rng = np.random.default_rng(5)
    N, B = 7, 2
    with InferenceEngine(cfg, plan, params, max_batch=B,
                         cache_len=64) as eng:
        hs = [eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=3 + (i % 2))) for i in range(N)]
        outs = [h.result(timeout=180) for h in hs]
    assert all(isinstance(o, Request) and o.done for o in outs)
    cm = eng._cm
    assert cm.inserts == N and cm.evicts == N
    assert len(cm.free) == B and not cm.active
    # batched decode: far fewer ticks than sequential service would take
    assert eng.steps < sum(o.max_new_tokens for o in outs)


# -- SLO policies --------------------------------------------------------------
def test_shed_under_overload_returns_typed_overloaded(served):
    """A burst far past max_pending sheds with a typed Overloaded instead
    of queueing unboundedly; the engine still drains cleanly."""
    from repro.core.runtime import SLOPolicy
    cfg, plan, params = served
    rng = np.random.default_rng(6)
    N = 12
    with InferenceEngine(cfg, plan, params, max_batch=1, cache_len=64,
                         max_pending=2,
                         slo=SLOPolicy(degrade_at=0.5, shed_at=0.9)) as eng:
        hs = [eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=6)) for _ in range(N)]
        outs = [h.result(timeout=180) for h in hs]
    shed = [o for o in outs if isinstance(o, Overloaded)]
    done = [o for o in outs if isinstance(o, Request)]
    assert shed and done and len(shed) + len(done) == N
    assert eng.shed_count == len(shed)
    assert all("overloaded" in o.reason or "deadline" in o.reason
               for o in shed)
    # the ledger balances: nothing is silently dropped or still in flight
    assert eng._acct.in_flight() == 0


def test_degrade_caps_tokens_under_pressure(served):
    """At pressure level 1 (backlog past degrade_at) admission caps
    max_new_tokens and flags the request degraded."""
    from repro.core.runtime import SLOPolicy
    cfg, plan, params = served
    rng = np.random.default_rng(7)
    pol = SLOPolicy(degrade_at=0.25, shed_at=0.95, degrade_tokens=2)
    with InferenceEngine(cfg, plan, params, max_batch=1, cache_len=64,
                         max_pending=8, slo=pol) as eng:
        hs = [eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=40)) for _ in range(6)]
        outs = [h.result(timeout=180) for h in hs]
    done = [o for o in outs if isinstance(o, Request)]
    degraded = [o for o in done if o.degraded]
    assert degraded, "backlog never crossed degrade_at"
    assert all(len(o.tokens) <= pol.degrade_tokens for o in degraded)


def test_deadline_truncates_admitted_request(served):
    cfg, plan, params = served
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab, 6, dtype=np.int32)
    with InferenceEngine(cfg, plan, params, max_batch=2,
                         cache_len=64) as eng:
        # warm the jits so the deadline budget is spent decoding
        eng.submit(Request(prompt=prompt, max_new_tokens=2)).result(300)
        h = eng.submit(Request(prompt=prompt, max_new_tokens=5000,
                               deadline_s=0.25))
        out = h.result(timeout=120)
    assert isinstance(out, Request)
    assert out.finish_reason == "deadline"
    assert 0 < len(out.tokens) < 5000


# -- early exit ----------------------------------------------------------------
def test_early_exit_fires_and_caps_decode(served):
    """FastBERT-style exit: with a threshold below the model's observed
    confidence the request stops early; with an impossible threshold it
    runs to max_new_tokens."""
    cfg, plan, params = served
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, 6, dtype=np.int32)
    # measure this fixed-seed model's confidence on the first decode turn
    with InferenceEngine(cfg, plan, params, max_batch=1,
                         cache_len=64) as eng:
        eng.submit(Request(prompt=prompt, max_new_tokens=3)).result(300)
        conf = float(eng.state.last_conf[0])
    assert 0.0 < conf < 1.0

    with InferenceEngine(cfg, plan, params, max_batch=1, cache_len=64,
                         exit_threshold=conf * 0.5) as eng:
        out = eng.submit(Request(prompt=prompt,
                                 max_new_tokens=50)).result(300)
    assert out.finish_reason == "early_exit"
    assert len(out.tokens) < 50 and eng.early_exits == 1

    with InferenceEngine(cfg, plan, params, max_batch=1, cache_len=64,
                         exit_threshold=2.0) as eng:  # unreachable
        out = eng.submit(Request(prompt=prompt,
                                 max_new_tokens=4)).result(300)
    assert out.finish_reason == "max_tokens" and len(out.tokens) == 4


def test_per_request_exit_threshold_overrides_engine(served):
    cfg, plan, params = served
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, cfg.vocab, 6, dtype=np.int32)
    with InferenceEngine(cfg, plan, params, max_batch=1, cache_len=64,
                         exit_threshold=2.0) as eng:
        # the request relaxes the engine's unreachable threshold to 0:
        # any confidence exits on the first decode turn
        out = eng.submit(Request(prompt=prompt, max_new_tokens=50,
                                 exit_threshold=1e-9)).result(300)
    assert out.finish_reason == "early_exit"


# -- supervisor integration ----------------------------------------------------
def test_adaptive_engine_supervisor_stop_idempotent(served):
    cfg, plan, params = served
    rng = np.random.default_rng(11)
    eng = InferenceEngine(cfg, plan, params, max_batch=2, cache_len=64,
                          adaptive=True)
    with eng:
        out = eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=3)).result(timeout=300)
    assert out.done
    # wait() already stopped the supervisor; stop() again is a no-op, and
    # a second wait() must not wedge or raise
    eng.supervisor.stop()
    assert eng.wait(timeout=10) == 0


def test_cache_manager_stats_surface(served):
    """The CacheManager exposes cache occupancy + SLO blocks through the
    StageHandle surface the Supervisor samples."""
    cfg, plan, params = served
    rng = np.random.default_rng(12)
    with InferenceEngine(cfg, plan, params, max_batch=2,
                         cache_len=64) as eng:
        eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=3)).result(timeout=300)
        handles = eng._runner.stage_handles()
        cm = next(h for h in handles
                  if getattr(h, "slo_controllable", False))
        s = cm.stats()
    assert s["cache"]["slots"] == 2
    assert s["slo"]["capacity"] == eng.max_pending
    assert {"backlog", "in_flight", "shed", "pressure"} <= set(s["slo"])
    # pushing a pressure level through the handle reaches admission
    cm.set_pressure(2)
    assert eng._slo.ext_level == 2


def test_prefill_error_reaches_handle_as_that_exception(served):
    """A prefill that raises fails its own request with that exception (a
    compiler or device fault is not overload); the engine keeps serving and
    still drains."""
    cfg, plan, params = served
    rng = np.random.default_rng(13)

    class PrefillFault(RuntimeError):
        pass

    eng = InferenceEngine(cfg, plan, params, max_batch=2, cache_len=64)
    real = eng._prefill_node._prefill

    def flaky(p, prompt):
        if prompt.shape[1] == 5:
            raise PrefillFault("refused")
        return real(p, prompt)

    eng._prefill_node._prefill = flaky
    with eng:
        bad = eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 5, dtype=np.int32),
            max_new_tokens=3))
        good = eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
            max_new_tokens=3))
        with pytest.raises(PrefillFault, match="refused"):
            bad.result(timeout=300)
        assert len(good.result(timeout=300).tokens) == 3
    assert eng.shed_count == 0
    assert eng.stats()["requests"]["failed"] == 1
    with pytest.raises(PrefillFault):
        list(eng.results())
