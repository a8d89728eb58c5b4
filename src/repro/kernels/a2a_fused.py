"""Fused all-to-all dispatch/combine — one Pallas kernel for the whole hop.

``core.device.a2a_dispatch`` used to lower ``ff_a2a`` as four separate XLA
programs per batch: ``router_topk`` for the capacity positions, a scatter
into ``(nR, cap)`` expert lanes, a per-expert compute loop, and a gather to
combine back in stream order.  This kernel fuses the entire hop into a
single ``pallas_call``: route (softmax + top-1), capacity position, expert
compute, and combine all happen per token block while the activations are
hot, and the ``(nR, cap)`` lane buffer is never materialized in HBM.

The per-expert counters live in int32 VMEM scratch and carry across token
blocks (the grid's sequential dimension) — they ARE the bounded SPSC lanes
of FastFlow's all-to-all, reduced to their essence: each counter is a lane's
write cursor, monotonically claimed first-come-first-served as tokens
stream past, and a token finding its cursor at ``capacity`` is the
synchronous SPMD rendering of a blocked push (the host runtime would
back-pressure; a fixed-shape device program must drop and zero-fill).  The
lane *storage* disappears entirely: because every expert's output for a
token can be computed where the token already sits, "enqueue into the lane,
service it, collect" collapses into "compute and select", and only the
cursor — the one piece of state the queue semantics actually need — remains
in VMEM.

Combine is pure selection (top-1's normalized weight is identically 1.0),
so outputs are bit-identical to applying the routed expert directly under
the same jit — ``kernels/ref.a2a_fused_ref`` asserts exactly that (jitted:
eager mode rounds multiply-add chains without FMA contraction, a 1-ulp
eager-mode artifact, and production segments are always jitted) — and
``interpret`` is
resolved through :mod:`kernels.backend` so the CPU CI verifies the same
kernel body that lowers to Mosaic on a TPU host.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret


def _kernel(logits_ref, xs_ref, out_ref, keep_ref, counts_ref, *,
            fns, E, capacity, bt, in_shape, out_shape):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # -- route: softmax + top-1 (the router_topk math, K=1) -----------------
    logits = logits_ref[...].astype(jnp.float32)          # (bt, E)
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)    # (bt,)

    # -- capacity position: running VMEM lane cursors + rank in this block --
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)      # (bt, E)
    within = exclusive_rank(onehot)                       # (bt, E)
    base = counts_ref[...]                                # (E,)
    pos = jnp.sum((within + base[None, :]) * onehot, axis=-1)
    keep = pos < capacity                                 # (bt,)

    # -- expert compute + combine, in-register ------------------------------
    xs = xs_ref[...].reshape((bt,) + in_shape)
    sel = jnp.zeros((bt,) + out_shape[1:], out_ref.dtype)
    for j, fn in enumerate(fns):
        yj = jax.vmap(fn)(xs).reshape((bt,) + out_shape[1:])
        sel = jnp.where((idx == j).reshape((bt,) + (1,) * (sel.ndim - 1)),
                        yj, sel)
    mask = keep.reshape((bt,) + (1,) * (sel.ndim - 1))
    out_ref[...] = jnp.where(mask, sel, jnp.zeros_like(sel))
    keep_ref[...] = keep.reshape(bt, 1)
    counts_ref[...] = base + jnp.sum(onehot, axis=0)


def exclusive_rank(onehot):
    """Per-column exclusive running count of a ``(bt, E)`` 0/1 matrix: row
    ``t`` holds how many earlier rows share each column.  Mosaic has no
    ``cumsum`` lowering, so this is one MXU matmul with the strictly lower
    triangular ones matrix; 0/1 inputs are exact in bf16 and the f32
    accumulator is exact for any block under 2**24 rows."""
    bt = onehot.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    tril = (col < row).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        tril, onehot.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def _pick_block(T: int, block_t: Optional[int]) -> int:
    """Requested block, else 128 — snapped down to a multiple of 8 that
    divides T (the TPU's sublane tiling), or T itself when no such divisor
    fits."""
    if block_t is None:
        block_t = 128
    bt = min(block_t, T) // 8 * 8
    while bt and T % bt:
        bt -= 8
    return bt or T


def a2a_fused(logits, xs, expert_fns: Sequence[Callable], capacity: int, *,
              block_t: Optional[int] = None,
              interpret: Optional[bool] = None):
    """logits: (T, E); xs: (T, *item) already left-mapped items;
    ``expert_fns`` the E right workers (pure, array-in/array-out, agreeing
    on output shape/dtype).  Returns ``(out (T, *expert_out), keep (T,))``
    with over-capacity tokens zero-filled and ``keep=False``."""
    T, E = logits.shape
    if len(expert_fns) != E:
        raise ValueError(f"logits width {E} != {len(expert_fns)} experts")
    in_shape = xs.shape[1:]
    Din = int(math.prod(in_shape)) if in_shape else 1
    item = jax.ShapeDtypeStruct(in_shape, xs.dtype)
    outs = [jax.eval_shape(fn, item) for fn in expert_fns]
    if any(o.shape != outs[0].shape or o.dtype != outs[0].dtype
           for o in outs[1:]):
        raise ValueError("a2a experts must agree on output shape/dtype: "
                         f"{[(o.shape, str(o.dtype)) for o in outs]}")
    per_out = outs[0]
    Dout = int(math.prod(per_out.shape)) if per_out.shape else 1
    bt = _pick_block(T, block_t)
    nt = T // bt

    kernel = functools.partial(
        _kernel, fns=tuple(expert_fns), E=E, capacity=capacity, bt=bt,
        in_shape=in_shape, out_shape=(bt, Dout))
    out, keep = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0)),
                  pl.BlockSpec((bt, Din), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((bt, Dout), lambda t: (t, 0)),
                   pl.BlockSpec((bt, 1), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, Dout), per_out.dtype),
                   jax.ShapeDtypeStruct((T, 1), jnp.bool_)],
        scratch_shapes=[pltpu.VMEM((E,), jnp.int32)],
        interpret=default_interpret(interpret),
    )(logits, xs.reshape(T, Din))
    return out.reshape((T,) + per_out.shape), keep[:, 0]
