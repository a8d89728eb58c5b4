"""Open-loop traffic replay against the serving engine (paper Sec. 9 +
the SLO serving tier).

Closed-loop clients (wait for a response before sending the next request)
hide overload: the offered rate collapses to whatever the server sustains.
This bench replays an OPEN-loop Poisson arrival process — requests are
submitted on schedule regardless of completions — at a rate expressed as a
multiple of the engine's measured capacity, and reports what an SLO serving
tier must bound:

  latency_ms            p50 submit->finish latency of ADMITTED requests
  latency_p99_ms        p99 of the same (the SLO-relevant tail)
  goodput_items_per_s   finished (non-shed) requests per second
  shed                  requests refused with a typed ``Overloaded``

Under ``--overload 2`` (offered load = 2x capacity) a correct engine sheds
or degrades instead of queueing unboundedly: the admitted tail stays
bounded because the waiting backlog is capped, and host memory stays flat.
The smoke subset feeds CI's bench-compare gate (``latency_ms`` bounded,
``goodput_items_per_s`` no-regress) via the ``BENCH_graph.json`` artifact.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np


def _build_engine(max_batch=2, cache_len=64, **kw):
    from repro.configs import get
    from repro.core.plan import single_device_plan
    from repro.models.lm import LM
    from repro.serving import InferenceEngine

    cfg = get("ff-tiny").reduced()
    plan = single_device_plan()
    params = LM(cfg).init(jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, plan, params, max_batch=max_batch,
                          cache_len=cache_len, **kw)
    return cfg, eng


def _measure_capacity(cfg, eng, n=8, max_new=4, prompt_len=4):
    """Closed-loop warm-up: jit compile + a throughput estimate (req/s)
    the open-loop phase scales its offered rate from."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    hs = [eng.submit(Request(
        prompt=rng.integers(0, cfg.vocab, prompt_len, dtype=np.int32),
        max_new_tokens=max_new)) for _ in range(2)]
    for h in hs:
        h.result(timeout=300)           # compile happens here
    t0 = time.perf_counter()
    hs = [eng.submit(Request(
        prompt=rng.integers(0, cfg.vocab, prompt_len, dtype=np.int32),
        max_new_tokens=max_new)) for _ in range(n)]
    for h in hs:
        h.result(timeout=300)
    return n / (time.perf_counter() - t0)


def bench_serving(smoke: bool = True):
    from repro.core.runtime import SLOPolicy
    from repro.serving import Overloaded, Request

    n_requests = 24 if smoke else 96
    max_new = 4 if smoke else 8
    prompt_len = 4 if smoke else 16
    overload = 2.0
    cfg, eng = _build_engine(
        max_pending=8, slo=SLOPolicy(degrade_at=0.5, shed_at=0.9))
    rng = np.random.default_rng(1)
    with eng:
        cap = _measure_capacity(cfg, eng, max_new=max_new,
                                prompt_len=prompt_len)
        # open loop: Poisson arrivals at overload x measured capacity —
        # submissions happen on schedule whether or not the engine keeps up
        rate = cap * overload
        gaps = rng.exponential(1.0 / rate, n_requests)
        handles = []
        t0 = time.perf_counter()
        for i in range(n_requests):
            time.sleep(gaps[i])
            handles.append(eng.submit(Request(
                prompt=rng.integers(0, cfg.vocab, prompt_len,
                                    dtype=np.int32),
                max_new_tokens=max_new)))
        outs = [h.result(timeout=300) for h in handles]
        replay_s = time.perf_counter() - t0
    done = [o for o in outs if not isinstance(o, Overloaded)]
    shed = len(outs) - len(done)
    lats = sorted((o.finish_t - o.submit_t) * 1e3 for o in done)
    p50 = lats[len(lats) // 2] if lats else 0.0
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else 0.0
    goodput = len(done) / replay_s
    return [(
        "serving_open_loop", p50 * 1e3,
        f"{overload:.0f}x overload Poisson replay: {len(done)}/{n_requests} "
        f"admitted, {shed} shed, p50={p50:.0f}ms p99={p99:.0f}ms, "
        f"goodput={goodput:.1f} req/s (capacity~{cap:.1f} req/s)",
        {"latency_ms": round(p50, 2), "latency_p99_ms": round(p99, 2),
         "goodput_items_per_s": round(goodput, 3), "shed": shed},
    )]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--overload", type=float, default=2.0)
    ap.add_argument("--out", default=None,
                    help="optional standalone JSON artifact")
    args = ap.parse_args()
    results = {}
    print("name,us_per_call,derived")
    for name, us, derived, fields in bench_serving(args.smoke):
        rec = {"us_per_call": round(us, 2), "derived": derived}
        rec.update(fields)
        results[name] = rec
        print(f"{name},{us:.1f},{derived}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"bench": "serving", "smoke": args.smoke,
                       "results": results}, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
