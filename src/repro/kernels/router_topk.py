"""MoE top-k router + capacity dispatch — Pallas TPU kernel.

The farm emitter's ``selectworker`` as a kernel: per token block, compute
softmax + iterative top-k (K is small), then the capacity-bounded position
of every (token, k) slot in its expert lane.  The running per-expert
counters live in fp32/int32 VMEM scratch and carry across token blocks (the
grid's sequential dimension) — first-come-first-served lane occupancy,
exactly like the bounded SPSC queue it models.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .a2a_fused import exclusive_rank
from .backend import default_interpret

NEG_INF = -1.0e38


def _kernel(logits_ref, w_ref, idx_ref, pos_ref, keep_ref, counts_ref, *,
            K, E, capacity, bt):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    logits = logits_ref[...].astype(jnp.float32)          # (bt, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # iterative top-k (K small); the (bt, K) outputs are assembled by lane
    # select rather than stack/reshape, which Mosaic does not lower here
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, K), 1)
    masked = probs
    w = jnp.zeros((bt, K), jnp.float32)
    idx = jnp.zeros((bt, K), jnp.int32)
    hots = []
    for k in range(K):
        wk = jnp.max(masked, axis=-1)
        ik = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        w = jnp.where(lane == k, wk[:, None], w)
        idx = jnp.where(lane == k, ik[:, None], idx)
        hot = jax.nn.one_hot(ik, E, dtype=jnp.int32)      # (bt, E)
        hots.append(hot)
        masked = jnp.where(hot > 0, NEG_INF, masked)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    # positions in (token, k) order: the running expert counters, plus every
    # slot of earlier tokens in this block, plus this token's earlier slots
    total = sum(hots)                                     # (bt, E)
    earlier = exclusive_rank(total)                       # (bt, E)
    base = counts_ref[...]                                # (E,)
    pos = jnp.zeros((bt, K), jnp.int32)
    own = jnp.zeros((bt, E), jnp.int32)
    for k, hot in enumerate(hots):
        pk = jnp.sum((earlier + own + base[None, :]) * hot, axis=-1)
        pos = jnp.where(lane == k, pk[:, None], pos)
        own = own + hot

    w_ref[...] = w.astype(w_ref.dtype)
    idx_ref[...] = idx
    pos_ref[...] = pos
    keep_ref[...] = pos < capacity
    counts_ref[...] = base + jnp.sum(total, axis=0)


def router_topk(logits, top_k: int, capacity: int, *, block_t: int = 256,
                interpret: Optional[bool] = None):
    """logits: (T, E) -> (weights (T,K) f32, experts (T,K) i32,
    positions (T,K) i32, keep (T,K) bool).  ``interpret=None`` resolves via
    :mod:`kernels.backend`: Mosaic on TPU, Python interpreter elsewhere."""
    interpret = default_interpret(interpret)
    T, E = logits.shape
    bt = min(block_t, T)
    assert T % bt == 0, (T, bt)
    nt = T // bt
    kernel = functools.partial(_kernel, K=top_k, E=E, capacity=capacity,
                               bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0))],
        out_specs=[
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, top_k), jnp.float32),
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((T, top_k), jnp.bool_),
        ],
        scratch_shapes=[pltpu.VMEM((E,), jnp.int32)],
        interpret=interpret,
    )(logits)
