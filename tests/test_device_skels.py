"""Device-side skeleton lowerings.  Multi-device cases run in a subprocess
with fake XLA devices (the main test process keeps 1 device)."""

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.device import (expert_capacity, farm_map,
                               flash_decode_combine, feedback_scan,
                               tensor_map)


def test_farm_map_single_device(plan):
    f = farm_map(lambda x: x * 2, plan.mesh, axis="data")
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(f(x)), np.arange(8.0) * 2)


def test_tensor_map_reduce(plan):
    f = tensor_map(lambda a, b: a @ b, plan.mesh, axis="model",
                   split_spec=(P(None, "model"), P("model", None)),
                   compose="reduce")
    a = jnp.ones((4, 8))
    b = jnp.ones((8, 4))
    np.testing.assert_allclose(np.asarray(f(a, b)), np.full((4, 4), 8.0))


def test_feedback_scan_decode_loop():
    def step(state):
        return state + 1, state * 10
    final, emitted = feedback_scan(step, jnp.asarray(0), 5)
    assert int(final) == 5
    np.testing.assert_array_equal(np.asarray(emitted), [0, 10, 20, 30, 40])


def test_expert_capacity_bounds():
    c = expert_capacity(1024, 8, 2, 1.25)
    assert c % 8 == 0 and 0 < c <= 1024
    assert expert_capacity(16, 64, 8, 1.0) >= 8     # floor


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core.device import pipeline_shard, flash_decode_combine
    from jax import shard_map

    mesh = make_mesh((4, 2), ("stage", "model"))

    # --- pipeline skeleton: 4 stages, affine stage fn, vs serial oracle ----
    S, M, F = 4, 8, 16
    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, F, F)) * 0.3
    bs = jnp.zeros((S, F))
    params = {"w": ws, "b": bs}
    x_mb = jax.random.normal(jax.random.PRNGKey(1), (M, 4, F))

    run = pipeline_shard(stage_fn, mesh, "stage", n_microbatches=M)
    got = run(params, x_mb)

    ref = x_mb
    for s in range(S):
        ref = jax.vmap(lambda xx: stage_fn({"w": ws[s], "b": bs[s]}, xx))(ref)
    ok_pipe = bool(jnp.allclose(got, ref, atol=1e-5))

    # --- flash-decode combine: sharded-KV partial softmax == full softmax --
    B, H, Sk, D = 2, 4, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D))
    k = jax.random.normal(jax.random.PRNGKey(3), (B, Sk, H, D))
    v = jax.random.normal(jax.random.PRNGKey(4), (B, Sk, H, D))

    def local_attn(q, kl, vl):
        s = jnp.einsum("bhd,bkhd->bhk", q, kl) / jnp.sqrt(D)
        m = jnp.max(s, -1)
        p = jnp.exp(s - m[..., None])
        out = jnp.einsum("bhk,bkhd->bhd", p, vl) / jnp.maximum(
            jnp.sum(p, -1), 1e-30)[..., None]
        lse = jnp.log(jnp.sum(p, -1)) + m
        return flash_decode_combine(out, lse, "model")

    f = shard_map(local_attn, mesh=mesh,
                  in_specs=(P(), P(None, "model", None, None),
                            P(None, "model", None, None)),
                  out_specs=P(), check_vma=False)
    got2 = f(q, k, v)
    s = jnp.einsum("bhd,bkhd->bhk", q, k) / jnp.sqrt(D)
    p = jax.nn.softmax(s, -1)
    ref2 = jnp.einsum("bhk,bkhd->bhd", p, v)
    ok_fd = bool(jnp.allclose(got2, ref2, atol=1e-5))

    print(json.dumps({"pipe": ok_pipe, "flash_decode": ok_fd}))
""")


@pytest.mark.slow
def test_multi_device_pipeline_and_flash_decode():
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, cwd=".",
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["pipe"], "pipeline skeleton mismatch vs serial oracle"
    assert res["flash_decode"], "flash-decode combine mismatch"
