"""FastFlow *software accelerator* mode (paper Sec. 9) with the device mesh
as the accelerator, two ways:

1. raw JaxAccelerator: offload f(x) tasks (here: batched matmuls) and
   retrieve results asynchronously — the paper's offload/load_result
   pattern verbatim, with JAX async dispatch as the lock-free queue;
2. InferenceEngine: continuous-batching LM serving behind the typed
   client API — ``submit`` returns a ``RequestHandle``, ``results()``
   iterates outcomes, the engine is a context manager (the paper's
   offload/load_result surface remains available for compat).

    PYTHONPATH=src python examples/accelerator_offload.py
"""

import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.core import FF_EOS, JaxAccelerator
from repro.core.plan import single_device_plan
from repro.models.lm import LM
from repro.serving import InferenceEngine, Request


def demo_raw_accelerator():
    print("== raw accelerator: offloaded matmul stream ==")
    f = jax.jit(lambda x: (x @ x.T).sum(axis=1))
    acc = JaxAccelerator(f, max_inflight=8)
    acc.run_then_freeze()
    xs = [np.random.default_rng(i).normal(size=(256, 256)).astype(np.float32)
          for i in range(20)]
    t0 = time.perf_counter()
    for x in xs:
        acc.offload(x)          # returns immediately: async dispatch
    acc.offload(FF_EOS)
    n = 0
    while True:
        ok, r = acc.load_result()
        if not ok:
            break
        n += 1
    acc.wait()
    print(f"offloaded+retrieved {n} tasks in "
          f"{(time.perf_counter()-t0)*1e3:.1f} ms")
    assert n == len(xs)


def demo_serving():
    print("== inference engine: continuous batching ==")
    cfg = get("ff-tiny").reduced()
    plan = single_device_plan()
    params = LM(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    with InferenceEngine(cfg, plan, params, max_batch=2,
                         cache_len=64) as eng:
        for _ in range(5):
            eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 8,
                                                   dtype=np.int32),
                               max_new_tokens=8))
    # leaving the with-block drained the engine; outcomes replay in
    # completion order
    done = 0
    for req in eng.results():
        done += 1
        print(f"request {req.id}: {len(req.tokens)} tokens "
              f"[{req.finish_reason}] "
              f"({(req.finish_t-req.submit_t)*1e3:.0f} ms) {req.tokens[:8]}")
    assert done == 5
    print(f"engine decode steps: {eng.steps} (continuous batching: "
          f"fewer than sequential 5x8={5*8})")


if __name__ == "__main__":
    demo_raw_accelerator()
    demo_serving()
