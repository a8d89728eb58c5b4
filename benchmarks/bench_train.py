"""End-to-end step benchmarks on CPU (tiny configs): tokens/s through the
full train step and the serving engine — the 'whole system' numbers that
complement the per-layer rooflines."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.core.plan import single_device_plan
from repro.models.lm import LM
from repro.runtime.steps import (init_state, make_decode_step,
                                 make_prefill_step, make_train_step)


def bench_train_step():
    plan = single_device_plan()
    cfg = get("ff-tiny")
    state = init_state(cfg, plan, jax.random.PRNGKey(0))
    B, S = 4, 256
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab)}
    step = jax.jit(make_train_step(cfg, plan, lambda s: 1e-3))
    state, m = step(state, batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        state, m = step(state, batch)
    jax.block_until_ready(m["loss"])
    us = (time.perf_counter() - t0) / iters * 1e6
    toks = B * S
    return [("train_step_ff_tiny", us, f"{toks/(us/1e6)/1e3:.1f}ktok/s_cpu")]


def bench_decode_step():
    plan = single_device_plan()
    cfg = get("ff-tiny")
    params = LM(cfg).init(jax.random.PRNGKey(0))
    B, S, CL = 8, 64, 128
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    _, caches = jax.jit(make_prefill_step(cfg, plan, CL))(
        params, {"tokens": toks})
    decode = jax.jit(make_decode_step(cfg, plan, CL))
    tok = jnp.zeros((B, 1), jnp.int32)
    nt, lg, caches = decode(params, caches, {"token": tok,
                                             "pos": jnp.asarray(S)})
    jax.block_until_ready(nt)
    t0 = time.perf_counter()
    iters = 10
    for i in range(iters):
        nt, lg, caches = decode(params, caches,
                                {"token": nt, "pos": jnp.asarray(S + i)})
    jax.block_until_ready(nt)
    us = (time.perf_counter() - t0) / iters * 1e6
    return [("decode_step_ff_tiny_b8", us,
             f"{B/(us/1e6):.0f}tok/s_cpu")]
