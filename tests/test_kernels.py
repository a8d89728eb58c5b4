"""Per-kernel validation: shape/dtype sweeps, interpret=True kernels vs the
pure-jnp oracles in kernels/ref.py, plus gradient paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import flash_attention, router_topk, ssd_scan

pytestmark = pytest.mark.kernels


def _rnd(key, *shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,Hkv,Sq,Sk,D", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 256, 256, 64),     # GQA 2:1
    (1, 4, 1, 128, 256, 32),     # MQA, chunked-prefill alignment
    (1, 2, 2, 128, 128, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, Hkv, Sq, Sk, D, causal, window,
                                     dtype, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = _rnd(k1, B, H, Sq, D, dtype=dtype)
    k = _rnd(k2, B, Hkv, Sk, D, dtype=dtype)
    v = _rnd(k3, B, Hkv, Sk, D, dtype=dtype)
    o = flash_attention(q, k, v, causal, window, 128)
    r = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


def test_flash_attention_grad_finite(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = _rnd(k1, 1, 2, 128, 32)
    k = _rnd(k2, 1, 2, 128, 32)
    v = _rnd(k3, 1, 2, 128, 32)
    g = jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    for t in g:
        assert np.all(np.isfinite(np.asarray(t)))
    # grad matches grad of the oracle
    gr = jax.grad(lambda q, k, v: ref.attention_ref(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,H,S,N,P,chunk", [
    (1, 2, 128, 16, 32, 64),
    (2, 3, 256, 32, 64, 128),
    (1, 1, 64, 8, 8, 64),        # single chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(B, H, S, N, P, chunk, dtype, rng):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    q = _rnd(k1, B, H, S, N, dtype=dtype, scale=0.3)
    k = _rnd(k2, B, H, S, N, dtype=dtype, scale=0.3)
    v = _rnd(k3, B, H, S, P, dtype=dtype)
    la = (-jnp.abs(jax.random.normal(k4, (B, H, S))) * 0.1)
    o = ssd_scan(q, k, v, la, chunk)
    r = ref.ssd_scan_ref(q, k, v, la)
    tol = 2e-3 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("T,E,K,C,bt", [
    (256, 8, 2, 80, 128),
    (512, 16, 4, 150, 256),
    (128, 4, 1, 40, 128),
])
def test_router_matches_ref(T, E, K, C, bt, rng):
    logits = jax.random.normal(rng, (T, E))
    w, i, p, keep = router_topk(logits, K, C, bt)
    wr, ir, pr, keepr = ref.router_topk_ref(logits, K, C)
    assert np.array_equal(np.asarray(i), np.asarray(ir))
    assert np.array_equal(np.asarray(p), np.asarray(pr))
    assert np.array_equal(np.asarray(keep), np.asarray(keepr))
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("m,k,n,sizes", [
    (24, 64, 32, [3, 0, 5, 2]),          # tail unused, an empty group
    (24, 64, 32, [0, 0, 0, 0]),          # no group has a row
    (256, 200, 130, [100, 0, 156]),      # every row grouped, over 2 tiles
    (300, 1100, 96, [1, 129, 0, 7]),     # a group across a tile edge
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_ref(m, k, n, sizes, dtype, rng):
    """The grouped rows equal the oracle's; the tail is the caller's to
    leave unread (the kernel does not write it)."""
    from repro.kernels.grouped_matmul import grouped_matmul
    k1, k2 = jax.random.split(rng)
    lhs = _rnd(k1, m, k, dtype=dtype)
    rhs = _rnd(k2, len(sizes), k, n, dtype=dtype, scale=k ** -0.5)
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = int(sizes.sum())
    got = np.asarray(grouped_matmul(lhs, rhs, sizes), np.float32)[:rows]
    want = np.asarray(ref.grouped_matmul_ref(lhs, rhs, sizes),
                      np.float32)[:rows]
    np.testing.assert_allclose(got, want, rtol=TOL[dtype], atol=TOL[dtype])


def test_router_capacity_never_exceeded(rng):
    """Property: per-expert kept count <= capacity, kept slots unique."""
    T, E, K, C = 512, 8, 2, 64
    logits = jax.random.normal(rng, (T, E)) * 3.0   # skewed -> drops happen
    w, i, p, keep = router_topk(logits, K, C, 256)
    i, p, keep = map(np.asarray, (i, p, keep))
    for e in range(E):
        kept = keep & (i == e)
        assert kept.sum() <= C
        slots = p[kept]
        assert len(set(slots.tolist())) == len(slots)   # unique lane slots
    assert keep.sum() > 0


@pytest.mark.parametrize("T,want", [(64, 64), (100, 100), (128, 128),
                                    (200, 40), (256, 128), (1000, 40)])
def test_pick_block_default(T, want):
    """With no block asked for, the a2a kernel's token block is 128 snapped
    down to a multiple of 8 that divides T, else T."""
    from repro.kernels.a2a_fused import _pick_block
    assert _pick_block(T, None) == want


def test_flash_attention_in_model_path(plan, rng):
    """The flash-attention kernel on the attention block's projections
    equals the block's XLA streaming path."""
    from repro.configs import get
    from repro.models import attention as A
    from repro.models.params import init_params
    cfg = get("ff-tiny").reduced()
    p = init_params(A.attn_defs(cfg, None), rng)
    B, S = 2, 64
    x = _rnd(rng, B, S, cfg.d_model, dtype=jnp.bfloat16, scale=0.3)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out_xla, _ = A.attention(x, p, cfg, plan, positions=pos, q_block=32,
                             kv_block=32)
    # kernel path
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    from repro.models.layers import apply_rope
    q, k = apply_rope(q, pos, cfg.rope_theta), apply_rope(k, pos,
                                                          cfg.rope_theta)
    o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), True, 0, 32)
    out_k = jnp.einsum("bshk,hkd->bsd", o.transpose(0, 2, 1, 3), p["wo"])
    np.testing.assert_allclose(np.asarray(out_xla, np.float32),
                               np.asarray(out_k, np.float32),
                               rtol=3e-2, atol=3e-2)
