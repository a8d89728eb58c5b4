"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are deliberately naive (materialize the score matrix, sequential
recurrences) — clarity over speed.  tests/test_kernels.py sweeps shapes and
dtypes asserting the kernels (interpret=True on CPU) match these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def attention_ref(q, k, v, *, causal=True, window=0):
    """q: (B,H,Sq,D); k,v: (B,Hkv,Sk,D); GQA by head repetition."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)   # q aligned to the end of k
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def ssd_scan_ref(q, k, v, log_a):
    """Sequential gated linear recurrence (the oracle for the chunked
    kernel):  h_t = a_t h_{t-1} + k_t v_t^T ; y_t = q_t . h_t.
    q,k: (B,H,S,N); v: (B,H,S,P); log_a: (B,H,S)."""
    B, H, S, N = q.shape
    P = v.shape[-1]

    def step(h, xs):
        qt, kt, vt, lat = xs
        h = jnp.exp(lat)[..., None, None] * h \
            + kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", qt, h)
        return h, y

    qs = jnp.moveaxis(q.astype(jnp.float32), 2, 0)
    ks = jnp.moveaxis(k.astype(jnp.float32), 2, 0)
    vs = jnp.moveaxis(v.astype(jnp.float32), 2, 0)
    las = jnp.moveaxis(log_a.astype(jnp.float32), 2, 0)
    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (qs, ks, vs, las))
    return jnp.moveaxis(ys, 0, 2).astype(q.dtype)   # (B,H,S,P)


def a2a_fused_ref(logits, xs, expert_fns, capacity: int):
    """Naive oracle for the fused all-to-all hop: top-1 route per token,
    first-come capacity position, routed expert applied directly, dropped
    tokens zero-filled.  logits: (T, E); xs: (T, *item).  Returns
    ``(out, keep)`` — the kernel must match bit-for-bit (combine is pure
    selection, never arithmetic)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)          # (T,)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1     # FCFS rank
    keep = pos < capacity
    outs = jnp.stack([jax.vmap(fn)(xs) for fn in expert_fns])   # (E, T, ...)
    out = outs[0]
    for j in range(1, E):
        sel = (idx == j).reshape((T,) + (1,) * (out.ndim - 1))
        out = jnp.where(sel, outs[j], out)
    mask = keep.reshape((T,) + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, jnp.zeros_like(out)), keep


def router_topk_ref(logits, top_k: int, capacity: int):
    """Top-k routing with capacity-bounded positions (first-come order).
    logits: (T, E) fp32.  Returns (weights (T,K), experts (T,K),
    positions (T,K), keep (T,K))."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    keep = pos < capacity
    return (w, idx, pos.reshape(T, top_k).astype(jnp.int32),
            keep.reshape(T, top_k))


def grouped_matmul_ref(lhs, rhs, sizes):
    """Each row of ``lhs`` times its group's matrix of ``rhs``: rows sorted
    by group, ``sizes[g]`` rows in group g; rows past ``sizes.sum()`` give
    0.  lhs (m, k), rhs (G, k, n), sizes (G,) -> (m, n) in lhs.dtype."""
    G = rhs.shape[0]
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(lhs.shape[0]),
                             side="right")                       # (m,)
    w = rhs[jnp.minimum(group, G - 1)].astype(jnp.float32)       # (m, k, n)
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32), w)
    return jnp.where((group < G)[:, None], out, 0.0).astype(lhs.dtype)
