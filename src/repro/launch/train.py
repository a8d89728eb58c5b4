"""Training launcher: the whole-stack driver behind ``--arch``.

    PYTHONPATH=src python -m repro.launch.train --arch ff-tiny --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --reduced
    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --batch 8 --seq 2048 --steps 20

One process drives every local device: a data-parallel mesh with FSDP
parameter sharding over all of them, or the single device when there is
one.  Configs run at their published widths unless ``--reduced`` is given.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile

import jax

from ..configs import get
from ..core.plan import ShardingPlan, single_device_plan
from ..data import SyntheticLMSource, make_pipeline
from ..optim.schedules import cosine_warmup
from ..runtime.driver import DriverConfig, TrainDriver
from ..runtime.steps import init_state, make_train_step, state_shardings
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh


def local_plan(n_devices: int) -> ShardingPlan:
    """Data-parallel FSDP plan over the first ``n_devices`` local devices
    (the single-device plan when that is one)."""
    if n_devices > 1:
        return ShardingPlan(mesh=make_host_mesh(data=n_devices))
    return single_device_plan()


def train(cfg, plan: ShardingPlan, *, steps: int, batch: int, seq: int,
          lr: float, ckpt_dir: str, ckpt_every: int, adaptive: bool = False,
          seed: int = 0):
    """Train ``cfg`` for ``steps`` steps on synthetic tokens made from
    ``seed``: the data pipeline feeds the fault-tolerant driver, which runs
    the jitted step with the train state donated (learning rate warmed up
    over the first fifth of the run, at most 20 steps).  Returns the
    driver's result (``history`` holds the per-step losses), the data
    pipeline and the final train state."""
    state = jax.device_put(init_state(cfg, plan, jax.random.PRNGKey(seed)),
                           state_shardings(cfg, plan))
    src = SyntheticLMSource(cfg.vocab, seq, batch, seed=seed)
    pipe = make_pipeline(src, plan, n_batches=steps + 8, adaptive=adaptive)
    warmup = min(20, max(1, steps // 5))
    step = jax.jit(make_train_step(cfg, plan, cosine_warmup(lr, warmup,
                                                            steps)),
                   donate_argnums=0)
    driver = TrainDriver(step, state, pipe,
                         DriverConfig(total_steps=steps,
                                      ckpt_every=ckpt_every,
                                      ckpt_dir=ckpt_dir))
    out = driver.run()
    return out, pipe, driver.state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ff-tiny")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized reduction of the arch")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(),
                                         "repro_train_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive data pipeline: a runtime Supervisor "
                         "re-places eligible farm stages live and feeds "
                         "observed costs back into the calibration cache")
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
    n_dev = len(jax.devices())
    plan = local_plan(n_dev)
    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.2f}M "
          f"devices={n_dev}")
    out, pipe, _ = train(cfg, plan, steps=args.steps, batch=args.batch,
                         seq=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         adaptive=args.adaptive)
    print(f"data graph: {pipe.graph.describe()}")
    for desc, p in pipe.placements:
        print(f"  [{p.target:6s}] {desc}")
    losses = [h["loss"] for h in out["history"]]
    print(f"final step {out['final_step']}: loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}; restarts={out['restarts']} "
          f"stragglers={out['stragglers']}")
    print("data graph stats (svc-time EMA / items / lane depths):")
    stats = pipe.stats()
    print("  " + json.dumps(stats, default=str))
    # boundary stall report: where the host<->device hop is stall-bound
    # (submit = stack+put+dispatch, drain = compute remainder + d2h copy,
    # stall = drain paid while the in-flight window was full)
    def _boundaries(x, out):
        if isinstance(x, dict):
            if "boundary" in x:
                out.append((x.get("node", "device"), x["boundary"]))
            for v in x.values():
                _boundaries(v, out)
        elif isinstance(x, (list, tuple)):
            for v in x:
                _boundaries(v, out)
    bnds = []
    _boundaries(stats, bnds)
    for node, b in bnds:
        print(f"  boundary[{node}] {b.get('mode')}: "
              f"microbatch={b.get('microbatch')} inflight={b.get('inflight')}"
              f" submit={b.get('submit_s', 0.0):.4f}s "
              f"drain={b.get('drain_s', 0.0):.4f}s "
              f"stall={b.get('stall_frac', 0.0):.0%} of drain")
    if args.adaptive:
        pipe.stop()                 # joins the supervisor, persists observe()
        events = pipe.replacement_events()
        print(f"re-placement events: {len(events)}")
        for e in events:
            print(f"  {e}")


if __name__ == "__main__":
    main()
