#!/usr/bin/env python3
"""Bring-up check on a TPU: the system's main paths, once each, at sizes a
user would run, each checked against a reference.

    python3 chip_smoke.py               # one chip: serving, training, graph
    python3 chip_smoke.py --four-chips  # four chips: data-parallel training
                                        # against the same run on one chip

One chip (no arguments):

- serving: ``llama3.2-3b`` at its published widths and full depth behind the
  continuous-batching engine (``repro.launch.serve``), 16 requests of 128
  prompt tokens and 32 new tokens at batch 8 and cache length 1024.  No
  request may be shed, and every token must equal a plain greedy loop over
  the same prefill and decode steps; where one differs, the reference's
  top-2 logit margin at that step must be within bf16 rounding.
- training: ``xlstm-125m`` at its published widths through
  ``repro.launch.train`` (data pipeline -> fault-tolerant driver -> jitted
  step with the state donated), batch 8 x 2048 tokens.  The loss must stay
  finite and fall.
- stream graph: a host process farm, a fused device segment and a device
  ``all_to_all`` lowered to the ``a2a_fused`` Pallas kernel compiled by
  Mosaic, at 4096 items of width 1024 per dispatch.  Items and their order
  must equal the host-thread lowering of the same graph exactly.

Four chips (``--four-chips``): only the data-parallel FSDP training path of
``repro.launch.train`` over four chips, with its per-step losses compared to
the same config, data and global batch on one chip.

Each phase prints what it ran, on which device, its compile seconds and its
rate; these are bring-up observations, not benchmark metrics.  The last line
of standard output is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU; otherwise the script exits non-zero without it.
Every process this script starts is a host-side worker that never touches
the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

SERVE_ARCH, SERVE_BATCH, SERVE_CACHE = "llama3.2-3b", 8, 1024
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW = 16, 128, 32
TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = "xlstm-125m", 8, 2048, 12
# the 4-vs-1-chip comparison trains at a tenth of the learning rate: at
# full rate Adam's first steps from random weights are chaotic (the loss
# jumps back up), which amplifies reduction-order roundoff until runs that
# are both right disagree by more than bf16 precision
FOUR_CHIP_STEPS, FOUR_CHIP_LR, TRAIN_LR = 6, 3e-4, 3e-3
GRAPH_T, GRAPH_D, GRAPH_EXPERTS, GRAPH_DISPATCHES = 4096, 1024, 8, 2
SEED = 0


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_line(jax) -> str:
    d = jax.devices()
    return f"{d[0].platform} {d[0].device_kind} x{len(d)}"


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 numbers (8 significant bits) at magnitude x."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


def greedy_reference(jax, jnp, np, cfg, params, prompt, n_new, batch,
                     cache_len, prefill, decode):
    """Greedy decoding of one prompt by a plain loop over the jitted prefill
    and decode steps, the request in row 0 of a decode batch as wide as the
    engine's.  Returns (tokens, top-2 logit margin and top-1 logit at each
    token)."""
    from repro.configs import cache_specs
    logits, cache1 = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    caches = jax.tree.map(
        lambda s, c: jnp.zeros(s.shape, s.dtype).at[:, :1].set(c),
        cache_specs(cfg, batch, cache_len), cache1)
    toks, margins = [], []
    row = np.asarray(logits[0, -1], np.float32)
    pos = len(prompt)
    for i in range(n_new):
        top2 = np.sort(row)[-2:]
        toks.append(int(np.argmax(row)))
        margins.append((float(top2[1] - top2[0]), float(top2[1])))
        if i == n_new - 1:
            break
        tok = jnp.zeros((batch, 1), jnp.int32).at[0, 0].set(toks[-1])
        p = jnp.zeros((batch,), jnp.int32).at[0].set(pos + i)
        _, logits, caches = decode(params, caches, {"token": tok, "pos": p})
        row = np.asarray(logits[0, -1], np.float32)
    return toks, margins


def phase_serving(jax) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get
    from repro.launch.serve import make_engine, serve
    from repro.runtime.steps import make_decode_step, make_prefill_step
    from repro.serving import Overloaded

    cfg = get(SERVE_ARCH)
    t0 = time.perf_counter()
    eng = make_engine(cfg, seed=SEED, max_batch=SERVE_BATCH,
                      cache_len=SERVE_CACHE)
    jax.block_until_ready(eng.params)
    n_params = sum(int(p.size) for p in jax.tree.leaves(eng.params))
    log("serving", f"device={device_line(jax)} arch={cfg.name} "
        f"params={n_params} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab} "
        f"batch={SERVE_BATCH} cache_len={SERVE_CACHE} "
        f"requests={SERVE_REQUESTS} prompt={SERVE_PROMPT} "
        f"new={SERVE_NEW} init_s={time.perf_counter() - t0:.1f}")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, SERVE_PROMPT, dtype=np.int32)
               for _ in range(SERVE_REQUESTS)]
    outcomes, compile_s, serve_s = serve(eng, prompts, SERVE_NEW)
    shed = [o for o in outcomes if isinstance(o, Overloaded)]
    if shed:
        raise AssertionError(f"{len(shed)} requests shed: {shed[0].reason}")
    if len(outcomes) != SERVE_REQUESTS:
        raise AssertionError(f"{len(outcomes)} of {SERVE_REQUESTS} "
                             "requests came back")
    n_tok = sum(len(o.tokens) for o in outcomes)
    log("serving", f"compile_s={compile_s:.1f} served {len(outcomes)} "
        f"requests, {n_tok} tokens in {serve_s:.2f}s "
        f"({n_tok / serve_s:.1f} tokens/s) decode_steps={eng.steps} "
        f"shed=0")

    params, plan = eng.params, eng.plan
    del eng
    prefill = jax.jit(make_prefill_step(cfg, plan, SERVE_CACHE))
    decode = jax.jit(make_decode_step(cfg, plan, SERVE_CACHE),
                     donate_argnums=1)
    exact = ties = 0
    for out, prompt in zip(outcomes, prompts):
        ref, margins = greedy_reference(
            jax, jnp, np, cfg, params, prompt, SERVE_NEW,
            SERVE_BATCH, SERVE_CACHE, prefill, decode)
        if len(out.tokens) != len(ref):
            raise AssertionError(f"request {out.id}: {len(out.tokens)} "
                                 f"tokens, reference {len(ref)}")
        diff = next((i for i, (a, b) in enumerate(zip(out.tokens, ref))
                     if a != b), None)
        if diff is None:
            exact += 1
            continue
        margin, top1 = margins[diff]
        tol = 2.0 * bf16_ulp(top1)
        log("serving", f"request {out.id} differs at token {diff}: engine "
            f"{out.tokens[diff]} reference {ref[diff]}, reference top-2 "
            f"margin {margin:.6g} (bf16 rounding bound {tol:.6g})")
        if margin > tol:
            raise AssertionError(f"request {out.id} token {diff} differs "
                                 f"with margin {margin} > {tol}")
        ties += 1
    log("serving", f"reference: {exact}/{len(outcomes)} requests "
        f"token-identical, {ties} diverged at a bf16 tie")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def run_training(jax, n_devices: int, steps: int, lr: float, tag: str):
    from repro.configs import get
    from repro.launch.train import local_plan, train

    cfg = get(TRAIN_ARCH)
    plan = local_plan(n_devices)
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        t0 = time.perf_counter()
        out, _pipe, state = train(
            cfg, plan, steps=steps, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
            lr=lr, ckpt_dir=ckpt, ckpt_every=10 ** 9, seed=SEED)
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    dts = sorted(h["dt"] for h in hist[1:]) or [hist[0]["dt"]]
    step_s = dts[len(dts) // 2]
    log(tag, f"device={device_line(jax)} mesh={dict(plan.mesh.shape)} "
        f"arch={cfg.name} params={cfg.n_params()} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} batch={TRAIN_BATCH} seq={TRAIN_SEQ} "
        f"lr={lr} steps={len(losses)} first_step_s={hist[0]['dt']:.1f} "
        f"median_step_s={step_s:.4f} "
        f"tokens/s={TRAIN_BATCH * TRAIN_SEQ / step_s:.0f} wall_s={wall:.1f}")
    log(tag, "losses " + " ".join(f"{l:.5f}" for l in losses))
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    return losses, state, plan


def phase_training(jax) -> None:
    losses, _, _ = run_training(jax, 1, TRAIN_STEPS, TRAIN_LR, "training")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")


def phase_four_chips(jax) -> None:
    if len(jax.devices()) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, have "
                             f"{len(jax.devices())}")
    one, _, _ = run_training(jax, 1, FOUR_CHIP_STEPS, FOUR_CHIP_LR,
                             "train-1chip")
    four, state, plan = run_training(jax, 4, FOUR_CHIP_STEPS, FOUR_CHIP_LR,
                                     "train-4chip")
    devices = set(plan.mesh.devices.flat)
    leaves = jax.tree.leaves(state)
    off = [l for l in leaves if set(l.sharding.device_set) != devices]
    if off:
        raise AssertionError(f"{len(off)} state arrays not on all four "
                             "chips")
    split = sum(1 for l in leaves if not l.sharding.is_fully_replicated)
    log("train-4chip", f"state: {len(leaves)} arrays on "
        f"{len(devices)} chips, {split} of them sharded (FSDP)")
    if not split:
        raise AssertionError("no state array is sharded across chips")
    worst = 0.0
    for i, (a, b) in enumerate(zip(one, four)):
        rel = abs(a - b) / abs(a)
        worst = max(worst, rel)
        log("train-4chip", f"step {i}: 1 chip {a:.5f} 4 chips {b:.5f} "
            f"rel diff {rel:.2e}")
    # bf16 keeps 8 significant bits: a relative unit roundoff of 2**-8
    # per rounding; allow a few roundings' worth of drift over the steps
    tol = 4 * 2.0 ** -8
    if worst > tol:
        raise AssertionError(f"4-chip losses differ from 1-chip by {worst} "
                             f"(> {tol})")
    log("train-4chip", f"per-step losses agree: worst rel diff {worst:.2e} "
        f"<= {tol:.2e}")


# ---------------------------------------------------------------------------
# stream graph
# ---------------------------------------------------------------------------
def _host_stage(x):
    """Host farm worker (numpy only): remaps every lane but the key."""
    y = x.copy()
    y[1:] = (y[1:] * 5.0 + 3.0) % 16.0
    return y


def build_graph(jnp, lax, W):
    """Host farm -> two device stages -> all_to_all over the experts.  Lane
    0 of every item carries its stream index through every stage
    unchanged but for a monotone map, so the host lowering's arrival order
    can be put back into stream order.  All values are small integers or
    halves, exact in float32, so both lowerings must agree bit for bit."""
    from repro.core import all_to_all, farm, pipeline

    def expert(j):
        def f(y):
            key = lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 1) == 0
            return jnp.where(key, y, y * (j + 2.0) - j)
        return f

    return pipeline(
        farm(_host_stage, n=2),
        lambda x: jnp.dot(x, W, precision=lax.Precision.HIGHEST),
        lambda y: y * 0.5 + 1.0,
        all_to_all([lambda y: y - 1.0],
                   [expert(j) for j in range(GRAPH_EXPERTS)],
                   router=lambda y, n: jnp.asarray(
                       jnp.sum(y[1:9]), jnp.int32) % n))


def phase_graph(jax) -> None:
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from repro.core.compiler import CompileConfig, _DeviceStageNode
    from repro.core.plan import single_device_plan
    from repro.kernels.backend import default_interpret

    T, D = GRAPH_T, GRAPH_D
    n = T * GRAPH_DISPATCHES
    rng = np.random.default_rng(SEED)
    W = (rng.random((D, D)) < 1 / 8).astype(np.float32)
    W[:, 0] = 0.0
    W[0, :] = 0.0
    W[0, 0] = 1.0                      # lane 0 passes through the matmul
    W = jnp.asarray(W)
    xs = rng.integers(0, 16, (n, D)).astype(np.float32)
    xs[:, 0] = np.arange(n)
    items = list(xs)
    plan = single_device_plan()

    def compiled(mode):
        g = build_graph(jnp, lax, W)
        if mode == "host":
            return g.compile(config=CompileConfig(mode="host"))
        return g.compile(config=CompileConfig(
            plan=plan, device_batch=T, normalize=False,
            placements={0: "host_process", 1: "device", 2: "device",
                        3: "device"}))

    warm = compiled("device")
    targets = [p.target for _, p in warm.placements]
    t0 = time.perf_counter()
    warm.run(items[:T])
    compile_s = time.perf_counter() - t0
    seg = [m for m in warm._top_members() if isinstance(m, _DeviceStageNode)]
    if len(seg) != 1:
        raise AssertionError(f"{len(seg)} device segments, expected one "
                             "fused segment")
    hlo = seg[0]._batched.lower(
        jax.ShapeDtypeStruct((T, D), jnp.float32), jnp.int32(0)).as_text()
    mosaic = "tpu_custom_call" in hlo and not default_interpret()
    dev = compiled("device")
    t0 = time.perf_counter()
    out = dev.run(items)
    dev_s = time.perf_counter() - t0
    log("graph", f"device={device_line(jax)} placements={targets} items={n} "
        f"width={D} experts={GRAPH_EXPERTS} items_per_dispatch={T} "
        f"a2a_fused_mosaic={mosaic} compile_s={compile_s:.1f} "
        f"device_path_s={dev_s:.2f} ({n / dev_s:.0f} items/s)")

    t0 = time.perf_counter()
    ref = compiled("host").run(items)
    host_s = time.perf_counter() - t0
    got = [np.asarray(y) for y in out]
    want = sorted((np.asarray(y) for y in ref), key=lambda y: float(y[0]))
    keys = np.array([y[0] for y in got])
    if len(got) != n or len(want) != n:
        raise AssertionError(f"{len(got)} device items, {len(want)} host "
                             f"items, {n} sent")
    if not np.array_equal(keys, np.arange(n) * 0.5):
        raise AssertionError("device path output is not in stream order")
    bad = sum(a.tobytes() != b.tobytes() for a, b in zip(got, want))
    log("graph", f"host-thread reference: {n} items in {host_s:.1f}s; "
        f"{n - bad}/{n} items bit-identical, device order == stream order")
    if bad:
        raise AssertionError(f"{bad} items differ from the host lowering")
    if not mosaic:
        raise AssertionError("the device all_to_all did not run as a "
                             "Mosaic kernel")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel training "
                         "path and its one-chip comparison")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no usable backend: {e}",
              file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend: "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"jax {jax.__version__}; devices: {device_line(jax)}; compile "
          f"cache {enable_compile_cache()}", flush=True)

    phases = ([("four-chips", phase_four_chips)] if args.four_chips else
              [("serving", phase_serving), ("training", phase_training),
               ("graph", phase_graph)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(jax)
            log(name, f"PASS in {time.perf_counter() - t0:.1f}s")
        except Exception:   # noqa: BLE001 - report every phase, then fail
            traceback.print_exc()
            log(name, f"FAIL after {time.perf_counter() - t0:.1f}s")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
