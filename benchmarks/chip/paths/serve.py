"""The serving path: the continuous-batching engine (``serving/engine.py``)
behind its client API, driven by the cell's traffic.

Set-up makes the weights from the seed in one jitted call, builds the
engine and sends one request of every prompt length the mix can send, so
that every prefill shape, the slot insert and the decode step are compiled
(or loaded from the compile cache) before the window opens.

The window is ``--seconds`` long.  The mix is open loop: each request is
sent at its due time, whatever the engine has in hand.  Token times are taken on the client's side: the request
object that the benchmark submits stamps every token the engine appends to
it.

The check: once every request has an outcome and the engine's state is
freed, a sample of finished requests drawn from the seed (the longest
among them) is run through the plain float32 reference, and the widest gap
by which a served token's logit lies below the reference's best at that
position is compared with its limit.  With ``ctx.control`` the control is
judged by the same limit: the reference computed in float8, the precision
below the bfloat16 the configuration serves, put in the program's place.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable

import numpy as np

import harness
import traffic as T
import weights as W

GRACE_S = 60.0          # an open-loop request may finish this long after
SAMPLE_TOKENS = 768     # served tokens the check compares, at least
SAMPLE_MAX = 8          # requests the check compares, at most
INF_MS = 1e12           # a request that never got a token


def program_config(c: dict):
    """The program's config for ``c``, with every width checked against
    the configuration file."""
    from repro.configs import get
    p = c["program"]
    cfg = dataclasses.replace(get(p["registry"]), **p["overrides"])
    want = {"d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "n_layers": c["num_hidden_layers"],
            "rope_theta": c["rope_theta"], "act": c["hidden_act"],
            "tie_embeddings": c["tie_word_embeddings"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {p['registry']} differs from the "
                         f"configuration file: {got} != {want}")
    return cfg


def _request_class():
    from repro.serving import Request

    class Stamped(list):
        """A token list that stamps the time of every token put in it."""

        def __init__(self, items=()):
            super().__init__(items)
            now = time.perf_counter()
            self.times = [now] * len(self)

        def append(self, x):
            super().append(x)
            self.times.append(time.perf_counter())

        def extend(self, xs):
            xs = list(xs)
            super().extend(xs)
            now = time.perf_counter()
            self.times.extend([now] * len(xs))

        def __iadd__(self, xs):
            self.extend(xs)
            return self

    @dataclasses.dataclass
    class ClientRequest(Request):
        due_t: float = 0.0          # when it was due to be sent
        sent_t: float = 0.0         # when the client called submit

        def __setattr__(self, name, value):
            if name == "tokens" and value is not None \
                    and not isinstance(value, Stamped):
                value = Stamped(value)
            object.__setattr__(self, name, value)

    return ClientRequest


def _wait(handles, until: float) -> None:
    for h in handles:
        left = until - time.perf_counter()
        if left <= 0 or not h._event.wait(left):
            return


def _open_loop(eng, reqs, t0, t1, trace_stop: Callable) -> list:
    handles = []
    for r in reqs:
        due = t0 + r.due_t
        while True:
            left = due - time.perf_counter()
            if left <= 0:
                break
            time.sleep(left if left > 2e-3 else 0)
        r.due_t = due
        r.sent_t = time.perf_counter()
        r.deadline_s = t1 + GRACE_S - r.sent_t
        handles.append(eng.submit(r))
    left = t1 - time.perf_counter()
    if left > 0:
        time.sleep(left)
    trace_stop()
    _wait(handles, t1 + GRACE_S + 30.0)
    return handles


def _outcome(h):
    try:
        return h.result(0)
    except Exception as e:  # noqa: BLE001 - a failed request is counted
        return e


def _stats(reqs, outcomes, t0, t1) -> dict:
    from repro.serving import Request
    ttft, itl, lag = [], [], []
    tokens = decode_tokens = kv_positions = 0
    prompt_tokens = []
    failed = shed = 0
    for r, out in zip(reqs, outcomes):
        ok = isinstance(out, Request)
        times = r.tokens.times if r.tokens is not None else []
        if isinstance(out, Exception) or out is None:
            failed += 1
        elif not ok:
            shed += 1
        lag.append(r.sent_t - r.due_t)
        ttft.append((times[0] - r.due_t) * 1e3 if times else INF_MS)
        n = len(r.prompt)
        for j, t in enumerate(times):
            if t0 <= t <= t1:
                tokens += 1
                if j == 0:
                    prompt_tokens.append(n)
                else:
                    decode_tokens += 1
                    kv_positions += n + j
        for a, b in zip(times, times[1:]):
            if t0 <= b <= t1:
                itl.append((b - a) * 1e3)
    return {"ttft_ms": ttft, "itl_ms": itl, "send_lag_s": lag,
            "tokens": tokens, "decode_tokens": decode_tokens,
            "kv_positions": kv_positions, "prompt_lengths": prompt_tokens,
            "failed": failed, "shed": shed}


def _sample(reqs, outcomes, seed: int):
    """Finished requests for the check: the one with the most served
    tokens, then others in an order drawn from the seed."""
    from repro.serving import Request
    done = [r for r, o in zip(reqs, outcomes)
            if isinstance(o, Request) and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.tokens))
    rng = np.random.default_rng((int(seed) + 0x9E37) % (1 << 64))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [], 0
    for r in [longest] + rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def logit_gaps(ref_logits, served) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position."""
    return np.concatenate([lg.max(-1) - lg[np.arange(len(t)), t]
                           for lg, t in zip(ref_logits, served)])


def run(ctx) -> "harness.Run":
    import jax
    import jax.numpy as jnp
    from repro.core.plan import single_device_plan
    from repro.models.lm import LM
    from repro.serving import InferenceEngine

    c, mix = ctx.cell.config, ctx.cell.traffic
    ref = harness.load_module("refs", c["program"]["reference"],
                              ctx.cell.root)
    cfg = program_config(c)
    layout = ref.layout(c)
    W.check_layout(layout, LM(cfg).param_defs())
    dtype = jnp.dtype(c["program"]["dtype"])
    if mix["cache_len"] < T.max_context(mix):
        raise ValueError(f"cache_len {mix['cache_len']} < the mix's "
                         f"longest context {T.max_context(mix)}")
    vocab = c["vocab_size"]
    Req = _request_class()

    # -- set-up: weights, engine, every shape of the mix --------------------
    t = time.perf_counter()
    params = W.init_on_device(ctx.seed, layout, dtype)
    jax.block_until_ready(params)
    ctx.log(f"weights {sum(x.size for x in jax.tree.leaves(params))} "
            f"params in {time.perf_counter() - t:.2f}s")
    eng = InferenceEngine(cfg, single_device_plan(), params,
                          max_batch=mix["max_batch"],
                          cache_len=mix["cache_len"],
                          prefill_workers=c["serve"]["prefill_workers"])
    if ctx.hook is not None:
        ctx.hook(eng)
    eng.start()
    t = time.perf_counter()
    rng = np.random.default_rng(1)
    for n in T.ladder(mix):
        warm = Req(prompt=rng.integers(0, vocab, n, dtype=np.int32),
                   max_new_tokens=3)
        out = eng.submit(warm).result(timeout=1200)
        if len(out.tokens) != 3:
            raise RuntimeError(f"warm-up request of {n} tokens came back "
                               f"with {len(out.tokens)} tokens")
    ctx.log(f"warm-up of {len(T.ladder(mix))} prompt lengths in "
            f"{time.perf_counter() - t:.2f}s")
    reqs = [Req(prompt=r["prompt"], max_new_tokens=r["max_new"],
                due_t=r["due_s"])
            for r in T.serve_requests(mix, ctx.seed, ctx.seconds, vocab)]
    steps0 = eng.steps

    # -- the window ---------------------------------------------------------
    t0 = ctx.open_window()
    t1 = t0 + ctx.seconds
    handles = _open_loop(eng, reqs, t0, t1, ctx.close_window)
    sent = len(handles)
    reqs = reqs[:sent]
    outcomes = [_outcome(h) for h in handles]
    st = _stats(reqs, outcomes, t0, t1)
    steps = eng.steps - steps0
    device = ctx.device_info()
    estats = eng.stats()["requests"]
    eng.close()
    del eng, params, handles
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    ctx.log(f"window: sent {sent}, {st['tokens']} tokens, {steps} decode "
            f"steps, shed {st['shed']}, failed {st['failed']}, engine "
            f"{estats}; {live} bytes still on the chip")

    # -- the check ----------------------------------------------------------
    t = time.perf_counter()
    sample = _sample(reqs, outcomes, ctx.seed)
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in sample]
    pos = [np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens))
           for r in sample]
    served = [np.asarray(list(r.tokens), np.int64) for r in sample]
    counters, control = {}, None
    if sample:
        ref_logits = ref.logits_at(ctx.seed, c, seqs, pos, dtype, "f32")
        gap = float(logit_gaps(ref_logits, served).max())
        counters["check_tokens"] = int(sum(len(s) for s in served))
        if ctx.control:
            # the control in the program's place: the reference one
            # precision below, at the token it puts first at each position
            low = ref.logits_at(ctx.seed, c, seqs, pos, dtype, "fp8")
            picks = [lg.argmax(-1) for lg in low]
            cchecks = harness.judge(
                {"logit_gap": float(logit_gaps(ref_logits, picks).max())},
                ctx.cell.limits)
            control = {"checks": cchecks,
                       "correct": harness.checks_ok(cchecks)}
    else:
        gap = float("inf")
    checks = harness.judge({"logit_gap": gap}, ctx.cell.limits)
    ctx.log(f"check: {len(sample)} requests, "
            f"{counters.get('check_tokens', 0)} served tokens, widest "
            f"logit gap {gap:.6g}, in {time.perf_counter() - t:.1f}s")

    e2e = {"ttft_p95_ms": harness.percentile(st["ttft_ms"], 95),
           "itl_p95_ms": harness.percentile(st["itl_ms"], 95)
           if st["itl_ms"] else INF_MS,
           "tokens_per_s": st["tokens"] / ctx.seconds}
    counters.update(st, decode_steps=steps, window=(t0, t1), sent=sent,
                    prompt_lengths_sent=[len(r.prompt) for r in reqs])
    attempted = sent
    return harness.Run(
        end_to_end=e2e, counters=counters, checks=checks,
        attempted=attempted, failed=st["failed"] + st["shed"],
        device=device, correct=harness.checks_ok(checks) and st["failed"] == 0
        and sent > 0, control=control)
