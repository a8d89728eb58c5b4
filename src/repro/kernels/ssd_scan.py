"""Chunked gated linear recurrence (SSD / Mamba2 / mLSTM core) — Pallas TPU
kernel.

    h_t = exp(log_a_t) * h_{t-1} + k_t v_t^T ;   y_t = q_t . h_t

The chunk axis is the grid's sequential minor dimension: the (N, P) state
matrix lives in fp32 VMEM scratch and carries chunk-to-chunk — the feedback
(wrap_around) skeleton implemented at the register/VMEM level.  Intra-chunk
work is dense MXU matmuls (Q x Q decay-masked scores), exactly mirroring
models/ssm.chunked_gla; the oracle is kernels/ref.ssd_scan_ref.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret


def _kernel(q_ref, k_ref, v_ref, la_row_ref, la_col_ref, y_ref, state_ref,
            *, Q):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (Q, N)
    k = k_ref[0, 0].astype(jnp.float32)          # (Q, N)
    v = v_ref[0, 0].astype(jnp.float32)          # (Q, P)
    la_row = la_row_ref[0, 0].astype(jnp.float32)    # (1, Q)
    la_col = la_col_ref[0, 0].astype(jnp.float32)    # (Q, 1)

    # inclusive cumsum of log_a along the chunk, as a row and as a column:
    # masked sums over the (Q, Q) causal triangle (Mosaic lowers neither
    # cumsum nor a (Q,) block, so log_a arrives in both 2-D layouts)
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = si <= ti
    cum = jnp.sum(jnp.where(causal, la_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(ti <= si, la_col, 0.0), axis=0, keepdims=True)
    tot = jnp.sum(la_row, axis=1, keepdims=True)     # (1, 1)

    # intra-chunk: scores[t,s] = q_t.k_s * exp(cum_t - cum_s), s <= t
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (Q, Q)
    decay = jnp.exp(jnp.clip(cum - cum_row, -60.0, 0.0))
    w = jnp.where(causal, s * decay, 0.0)
    y_intra = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())))

    # inter-chunk: y_t += exp(cum_t) q_t . h_in
    h_in = state_ref[...]
    y_inter = jnp.exp(jnp.clip(cum, -60.0, 0.0)) * \
        jax.lax.dot_general(q, h_in, (((1,), (0,)), ((), ())))

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h_out = exp(tot) h_in + sum_s exp(tot - cum_s) k_s v_s^T
    dk = jnp.exp(jnp.clip(tot - cum, -60.0, 0.0)) * k             # (Q, N)
    inc = jax.lax.dot_general(dk, v, (((0,), (0,)), ((), ())))    # (N, P)
    state_ref[...] = jnp.exp(jnp.clip(tot, -60.0, 0.0)) * h_in + inc


def ssd_scan(q, k, v, log_a, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """q,k: (B,H,S,N); v: (B,H,S,P); log_a: (B,H,S) -> y (B,H,S,P).
    ``interpret=None`` resolves via :mod:`kernels.backend` (Mosaic on TPU)."""
    interpret = default_interpret(interpret)
    B, H, S, N = q.shape
    P = v.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    kernel = functools.partial(_kernel, Q=Q)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), q.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(q, k, v, log_a[:, :, None, :], log_a[..., None])
