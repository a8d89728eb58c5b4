"""Serving launcher: the continuous-batching engine behind the typed
client API (``submit`` -> ``RequestHandle``, ``results()``, context-manager
lifecycle) — the paper's accelerator surface remains available on the
engine for compat.

    PYTHONPATH=src python -m repro.launch.serve --arch ff-tiny --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --max-batch 8 --cache-len 1024 --prompt-len 128 --max-new 32
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Tuple

import jax
import numpy as np

from ..configs import get
from ..core.plan import single_device_plan
from ..models.lm import LM
from ..serving import InferenceEngine, Overloaded, Request
from .compile_cache import enable_compile_cache


def make_engine(cfg, *, seed: int = 0, **engine_kw) -> InferenceEngine:
    """An :class:`InferenceEngine` over random parameters made from
    ``seed`` (parameters only: serving holds no optimizer state)."""
    plan = single_device_plan()
    params = LM(cfg).init(jax.random.PRNGKey(seed))
    return InferenceEngine(cfg, plan, params, **engine_kw)


def serve(eng: InferenceEngine, prompts, max_new: int,
          deadline: Optional[float] = None) -> Tuple[list, float, float]:
    """Serve one request per prompt and drain the engine.

    The first prompt is first served alone for two tokens, so that the
    engine's prefill, insert and decode steps compile before the timed
    window.  Returns every outcome (finished ``Request`` or
    ``Overloaded``) in submit order, the warm-up's seconds and the seconds
    from the first timed submit to the last outcome.  A request whose
    prefill failed raises its error here."""
    def request(prompt, n):
        return Request(prompt=np.asarray(prompt, np.int32),
                       max_new_tokens=n, deadline_s=deadline)

    with eng:
        t0 = time.perf_counter()
        warm = eng.submit(request(prompts[0], 2)).result() if prompts \
            else None
        if isinstance(warm, Overloaded):
            raise RuntimeError(f"warm-up request shed: {warm.reason}")
        t1 = time.perf_counter()
        handles = [eng.submit(request(p, max_new)) for p in prompts]
        outcomes = [h.result() for h in handles]
        t2 = time.perf_counter()
    return outcomes, t1 - t0, t2 - t1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ff-tiny")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized reduction of the arch")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request SLO deadline in seconds: past it a "
                         "request finishes truncated (or is shed before "
                         "admission)")
    ap.add_argument("--exit-threshold", type=float, default=None,
                    help="FastBERT-style early exit: stop decoding a "
                         "request once next-token confidence (max softmax "
                         "prob) reaches this")
    ap.add_argument("--adaptive", action="store_true",
                    help="attach the runtime Supervisor: live stage stats "
                         "sampling, SLO pressure-level control, cost-model "
                         "observation (events land in the report)")
    ap.add_argument("--tuned", action="store_true",
                    help="tuned host runtime: tcmalloc LD_PRELOAD when "
                         "installed + single-threaded XLA:CPU Eigen "
                         "(re-execs once; see repro.launch.tuned)")
    args = ap.parse_args()
    if args.tuned:
        from .tuned import apply_tuned
        apply_tuned()

    enable_compile_cache()
    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    eng = make_engine(cfg, max_batch=args.max_batch,
                      cache_len=args.cache_len, adaptive=args.adaptive,
                      exit_threshold=args.exit_threshold)
    print(f"engine graph: {eng.graph.describe()}")
    for desc, p in eng.placements:
        print(f"  [{p.target:6s}] {desc}")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len, dtype=np.int32)
               for _ in range(args.requests)]
    outcomes, compile_s, dt = serve(eng, prompts, args.max_new,
                                    args.deadline)
    total_toks = shed = 0
    for out in outcomes:
        if isinstance(out, Overloaded):
            shed += 1
            print(f"req {out.request.id}: SHED ({out.reason})")
            continue
        total_toks += len(out.tokens)
        print(f"req {out.id}: {len(out.tokens)} tokens "
              f"[{out.finish_reason}] in "
              f"{(out.finish_t - out.submit_t)*1e3:.0f} ms")
    print(f"warm-up (compile) {compile_s:.2f}s; served "
          f"{args.requests - shed}/{args.requests} requests, "
          f"{total_toks} tokens in {dt:.2f}s "
          f"({total_toks/max(dt, 1e-9):.1f} tok/s); "
          f"decode steps={eng.steps}, early exits={eng.early_exits}, "
          f"shed={eng.shed_count}")
    print("engine graph stats (svc-time EMA / cache occupancy / SLO):")
    print("  " + json.dumps(eng.stats(), default=str))
    if args.adaptive:
        events = eng.replacement_events()
        print(f"re-placement events: {len(events)}"
              + (f" (supervisor {eng.supervisor.stats()})"
                 if eng.supervisor else ""))
        for e in events:
            print(f"  {e}")


if __name__ == "__main__":
    main()
