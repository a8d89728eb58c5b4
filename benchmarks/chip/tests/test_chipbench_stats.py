"""Window arithmetic: percentiles and rates over all requests and the whole
window, and the client-side token clock of the serving path."""

import math

import pytest

import harness


def test_percentile_is_nearest_rank_over_all_values():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 95) == 95
    assert harness.percentile(vals, 99) == 99
    assert harness.percentile([3.0], 95) == 3.0
    assert harness.percentile(vals + [math.inf] * 6, 95) == math.inf
    with pytest.raises(ValueError):
        harness.percentile([], 95)


class _Req:
    """A served request as the serving path's statistics see it."""

    def __init__(self, due, token_times, prompt_len=10):
        self.prompt = [0] * prompt_len
        self.due_t = self.sent_t = due

        class _T(list):
            pass
        self.tokens = _T(range(len(token_times)))
        self.tokens.times = list(token_times)


def _stats(reqs, t0=0.0, t1=10.0):
    from repro.serving import Request
    serve = harness.load_module("paths", "serve")
    outs = [Request(prompt=None) for _ in reqs]
    return serve._stats(reqs, outs, t0, t1)


def _steady(n=40, stall_at=None, stall=0.0):
    """n requests due every 0.2 s, first token 50 ms after due, then a
    token every 20 ms; a stall delays every token after ``stall_at``."""
    reqs = []
    for i in range(n):
        due = 0.2 * i
        times = [due + 0.05 + 0.02 * k for k in range(5)]
        if stall_at is not None:
            times = [t + stall if t >= stall_at else t for t in times]
        reqs.append(_Req(due, times))
    return reqs


def test_a_stall_inside_the_window_moves_the_tails():
    calm = _stats(_steady())
    stalled = _stats(_steady(stall_at=4.06, stall=1.5))
    p = lambda xs: harness.percentile(xs, 95)  # noqa: E731
    assert p(calm["ttft_ms"]) == pytest.approx(50.0, abs=1e-6)
    assert p(calm["itl_ms"]) == pytest.approx(20.0, abs=1e-6)
    # the stall holds back requests due before it: their waits count from
    # when they were due, and the gap that spans it is one inter-token gap
    assert p(stalled["ttft_ms"]) > 1000.0
    assert max(stalled["itl_ms"]) > 1000.0
    assert p(stalled["itl_ms"]) >= p(calm["itl_ms"])


def test_only_gaps_that_end_in_the_window_count():
    reqs = [_Req(0.0, [9.0, 9.5, 10.5, 11.0])]
    st = _stats(reqs)
    assert st["itl_ms"] == [pytest.approx(500.0)]
    assert st["tokens"] == 2
    assert st["decode_tokens"] == 1 and st["kv_positions"] == 11


def test_a_request_with_no_token_is_infinitely_late():
    from repro.serving import Overloaded, Request
    serve = harness.load_module("paths", "serve")
    reqs = [_Req(0.0, [0.05, 0.07]), _Req(0.1, [])]
    outs = [Request(prompt=None), Overloaded(reqs[1], "overloaded")]
    st = serve._stats(reqs, outs, 0.0, 10.0)
    assert st["ttft_ms"][1] == serve.INF_MS
    assert st["shed"] == 1


def test_stamped_tokens_cover_append_extend_and_reassignment():
    serve = harness.load_module("paths", "serve")
    Req = serve._request_class()
    r = Req(prompt=[1, 2])
    r.tokens = []
    r.tokens.append(5)
    r.tokens.extend([6, 7])
    r.tokens += [8]
    assert list(r.tokens) == [5, 6, 7, 8]
    assert len(r.tokens.times) == 4
    assert r.tokens.times == sorted(r.tokens.times)
    r.tokens = [1, 2, 3]
    assert len(r.tokens.times) == 3


def test_a_number_is_judged_by_its_limit_and_fails_without_one():
    checks = harness.judge({"logit_gap": 0.04}, {"logit_gap": 0.1})
    assert checks == {"logit_gap": {"value": 0.04, "limit": 0.1}}
    assert harness.checks_ok(checks)
    assert not harness.checks_ok(harness.judge({"logit_gap": 0.2},
                                               {"logit_gap": 0.1}))
    assert not harness.checks_ok(harness.judge({"logit_gap": 0.0}, {}))
