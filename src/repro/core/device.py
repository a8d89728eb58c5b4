"""Device-side skeleton lowering: the FastFlow patterns expressed as SPMD
programs over a TPU mesh.

==================  ==========================================================
FastFlow skeleton    device lowering here
==================  ==========================================================
farm (DP)           ``farm_map`` — batch scatter (emitter) + psum collector
map  (Sec. 12.1)    ``tensor_map`` — shard_map Split/Compose over an axis
farm (EP/MoE)       dispatch/combine in models/moe.py (MPMC all-to-all);
                    helpers ``expert_capacity`` here
pipeline            ``pipeline_shard`` — stages on a mesh axis, microbatches
                    streamed over collective_permute edges (SPSC channels),
                    GPipe schedule with fill/drain bubbles
farm+collector      ``flash_decode_combine`` — partial-softmax workers +
                    logsumexp-combining collector for sharded-KV decode
feedback            ``feedback_scan`` — wrap_around as lax.scan carrying the
                    stream back (decode loop, divide&conquer);
                    ``feedback_while`` — the data-dependent variant as
                    lax.while_loop (per-item early exit, FastBERT-style)
==================  ==========================================================
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# farm over the data axis (the plain DP farm)
# ---------------------------------------------------------------------------
def farm_map(fn: Callable, mesh: Mesh, axis: str = "data",
             in_specs=None, out_specs=None, reduce_outputs: bool = False):
    """Run ``fn`` as farm workers over ``axis``; round-robin scheduling is the
    even batch sharding.  If ``reduce_outputs``, the collector psums results
    (gradient consolidation 'in memory', paper Sec. 8.2)."""
    in_specs = in_specs if in_specs is not None else P(axis)
    out_specs = out_specs if out_specs is not None else (P() if reduce_outputs else P(axis))

    def worker(*args):
        out = fn(*args)
        if reduce_outputs:
            out = jax.tree.map(lambda t: lax.pmean(t, axis), out)
        return out

    return shard_map(worker, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# map skeleton (Split -> workers -> Compose) over the model axis
# ---------------------------------------------------------------------------
def tensor_map(fn: Callable, mesh: Mesh, axis: str = "model",
               split_spec=None, compose: str = "gather", out_axis: int = -1):
    """Paper Sec. 12.1 map on a farm template: Split partitions the input over
    ``axis``; workers compute partitions; Compose rebuilds the result —
    ``gather`` (concatenate partitions, e.g. row-parallel) or ``reduce``
    (psum partial results, e.g. col-parallel matmul contributions)."""
    split_spec = split_spec if split_spec is not None else P(None, axis)

    def worker(*args):
        out = fn(*args)
        if compose == "reduce":
            out = jax.tree.map(lambda t: lax.psum(t, axis), out)
        return out

    if compose == "reduce":
        out_specs = P()
    else:  # gather: partitions concatenated along out_axis by the Compose
        ndim = (-out_axis) if out_axis < 0 else out_axis + 1
        spec = [None] * ndim
        spec[out_axis] = axis
        out_specs = P(*spec)
    return shard_map(worker, mesh=mesh, in_specs=split_spec,
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# pipeline skeleton over a mesh axis (pipeline parallelism)
# ---------------------------------------------------------------------------
def pipeline_shard(stage_fn: Callable, mesh: Mesh, axis: str,
                   n_microbatches: int):
    """GPipe-style pipeline: each shard along ``axis`` owns one stage's
    parameters; microbatches stream through ``collective_permute`` edges —
    the device SPSC channels.  Total steps = M + S - 1 (fill/drain bubble,
    cf. paper Sec. 13: service time = max stage time).

    ``stage_fn(stage_params, x) -> x`` must keep the activation shape.

    Returns ``run(stacked_stage_params, x_microbatches)`` where
    ``stacked_stage_params`` has a leading stage dim sharded over ``axis`` and
    ``x_microbatches`` is ``(M, mb, ...)`` replicated along ``axis``.
    """
    S = mesh.shape[axis]
    M = n_microbatches

    def body(params, x_mb):
        # params: this stage's slice (leading dim 1); x_mb: (M, mb, ...)
        params = jax.tree.map(lambda t: t[0], params)
        idx = lax.axis_index(axis)
        mb_shape = x_mb.shape[1:]
        state = jnp.zeros(mb_shape, x_mb.dtype)          # in-flight microbatch
        out = jnp.zeros_like(x_mb)                       # drained results
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def step(t, carry):
            state, out = carry
            # stage 0 ingests microbatch t (when available)
            ingress = x_mb[jnp.minimum(t, M - 1)]
            state = jnp.where((idx == 0) & (t < M), ingress, state)
            state = stage_fn(params, state)
            # last stage drains microbatch t-(S-1)
            done = t - (S - 1)
            take = (idx == S - 1) & (done >= 0)
            out = lax.dynamic_update_slice(
                out,
                jnp.where(take, state, lax.dynamic_slice(
                    out, (jnp.maximum(done, 0),) + (0,) * len(mb_shape),
                    (1,) + mb_shape)[0])[None],
                (jnp.maximum(done, 0),) + (0,) * len(mb_shape))
            # SPSC edge: push my state to the next stage
            state = lax.ppermute(state, axis, fwd_perm)
            return state, out

        state, out = lax.fori_loop(0, M + S - 1, step, (state, out))
        # Compose: broadcast the last stage's buffer (collector gather)
        if S > 1:
            out = lax.psum(jnp.where(idx == S - 1, out, jnp.zeros_like(out)),
                           axis)
        return out

    in_specs = (jax.tree.map(lambda _: P(axis), jax.tree.structure(0)), P())

    def run(stage_params, x_mb):
        specs = jax.tree.map(lambda _: P(axis), stage_params)
        f = shard_map(body, mesh=mesh, in_specs=(specs, P()),
                      out_specs=P(), check_vma=False)
        return f(stage_params, x_mb)

    return run


# ---------------------------------------------------------------------------
# farm-with-collector for sharded-KV decode (flash decoding)
# ---------------------------------------------------------------------------
def flash_decode_combine(partial_out: jnp.ndarray, partial_lse: jnp.ndarray,
                         axis: str):
    """Collector for context-parallel decode attention: workers hold KV
    shards and produce (softmax-partial output, logsumexp); the collector
    renormalizes — a farm whose collector implements a numerically exact
    gather policy.  Runs inside shard_map over ``axis``.

    partial_out: (..., d) local unnormalized-softmax output
    partial_lse: (...,)   local logsumexp of scores
    """
    m = lax.pmax(partial_lse, axis)
    w = jnp.exp(partial_lse - m)
    num = lax.psum(partial_out * w[..., None], axis)
    den = lax.psum(w, axis)
    return num / den[..., None]


# ---------------------------------------------------------------------------
# feedback channel (wrap_around) as a scan
# ---------------------------------------------------------------------------
def feedback_scan(step_fn: Callable, init_state, n_steps: int,
                  collect: bool = True):
    """Route the stream back to the input: ``state -> step_fn -> state``.
    Used for autoregressive decode (token fed back) and iterative
    divide&conquer refinement.  ``step_fn(state) -> (state, emit)``."""
    def body(state, _):
        state, emit = step_fn(state)
        return state, (emit if collect else None)

    return lax.scan(body, init_state, None, length=n_steps)


def feedback_while(step_fn: Callable, init_state, cond_fn: Callable,
                   max_steps: Optional[int] = None):
    """Data-dependent feedback channel: ``do {state = step(state)} while
    (cond(state))`` as a ``lax.while_loop`` — the device lowering of a
    ``wrap_around`` loop whose exit is decided per item per turn
    (``compile(feedback_cond=...)``), e.g. FastBERT-style confidence exit.

    The step always runs at least once, matching the host path where an
    item traverses the loop body before the runner evaluates the predicate
    on the feedback edge.  ``max_steps`` optionally caps the turn count
    (``feedback_steps`` riding along as a safety bound).

    vmap-safe by construction: under ``jax.vmap`` the batched loop keeps
    iterating until every lane's predicate is false, but a finished lane's
    state is frozen by the ``active`` mask — extra turns cannot corrupt it.
    ``step_fn(state) -> (state, emit)`` (emit discarded, as in
    ``feedback_scan(collect=False)``).  Returns ``(final_state, n_steps)``
    with ``n_steps`` the number of turns this item actually ran."""
    def body(carry):
        state, active, k = carry
        new_state, _ = step_fn(state)
        state = jax.tree.map(
            lambda old, new: jnp.where(active, new, old), state, new_state)
        k = k + jnp.asarray(active, jnp.int32)
        go = jnp.asarray(cond_fn(state), bool)
        if max_steps is not None:
            go = jnp.logical_and(go, k < max_steps)
        active = jnp.logical_and(active, go)
        return state, active, k

    init = (init_state, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    state, _, k = lax.while_loop(lambda c: jnp.any(c[1]), body, init)
    return state, k


# ---------------------------------------------------------------------------
# all-to-all (ff_a2a) as MoE-style dispatch/combine
# ---------------------------------------------------------------------------
def a2a_dispatch(left_fns: Sequence[Callable], right_fns: Sequence[Callable],
                 router: Optional[Callable] = None, mesh: Optional[Mesh] = None,
                 axis: str = "data", capacity_factor: Optional[float] = None,
                 interpret: Optional[bool] = None):
    """Device lowering of ``ff_a2a``: left workers map the batch, then the
    whole dispatch/combine hop — route, capacity position, expert compute,
    combine — runs as ONE fused Pallas kernel
    (:func:`~repro.kernels.a2a_fused.a2a_fused`, the ``router_topk``
    lane-occupancy math extended with in-kernel expert compute), sized by
    :func:`expert_capacity`.  The ``(nR, cap)`` lane buffer the old
    router-scatter-loop-gather lowering materialized in HBM no longer
    exists; only the per-expert VMEM cursors remain.

    Semantics mirror the host :class:`~repro.core.graph.A2ASkeleton`: item
    ``t`` enters left worker ``t % nL`` (the feeder's round-robin); without a
    ``router`` the default schedule matches the host's per-producer staggered
    round-robin ``(i + k) % nR``.  A ``router(item, n_right) -> int`` must be
    jax-traceable here (the host runtime accepts any Python callable).

    ``capacity_factor=None`` sizes every lane to the whole batch (lossless —
    the host runtime never drops, it blocks); with a factor, lanes are sized
    by :func:`expert_capacity` and items beyond capacity produce zeros, the
    bounded-lane drop policy of the synchronous SPMD rendering.

    Returns ``batched(xs, t_idx)`` mapping a stacked batch ``(T, ...)`` plus
    absolute stream indices ``(T,)`` to stacked outputs ``(T, ...)``; right
    workers must agree on output shape/dtype.  With a ``mesh``, the left map
    runs sharded over ``axis`` — and in the lossless case the fused
    dispatch/combine kernel runs sharded too (expert compute where the
    tokens already live: per-shard lane cursors reproduce the global
    first-come outcome exactly because nothing can overflow).  A bounded
    ``capacity_factor`` keeps the dispatch batch-global: first-come lane
    occupancy across shards needs the one set of cursors.
    """
    from ..kernels.a2a_fused import a2a_fused
    from ..kernels.backend import default_interpret

    interpret = default_interpret(interpret)
    nL, nR = len(left_fns), len(right_fns)

    def left_apply(x, t):
        if nL == 1:
            return left_fns[0](x)
        return lax.switch(t % nL, list(left_fns), x)

    def batched(xs, t_idx):
        T = xs.shape[0]
        axis_size = dict(mesh.shape).get(axis, 1) if mesh is not None else 1
        if axis_size > 1 and T % axis_size == 0:
            ys = farm_map(lambda a, b: jax.vmap(left_apply)(a, b), mesh,
                          axis=axis, in_specs=(P(axis), P(axis)),
                          out_specs=P(axis))(xs, t_idx)
        else:
            ys = jax.vmap(left_apply)(xs, t_idx)
        if router is not None:
            e = jax.vmap(lambda y: router(y, nR))(ys)
            e = jnp.asarray(e, jnp.int32) % nR
        else:  # host default: producer i's k-th output goes to (i + k) % nR
            e = (((t_idx % nL) + (t_idx // nL)) % nR).astype(jnp.int32)
        cap = T if capacity_factor is None else \
            expert_capacity(T, nR, 1, capacity_factor)
        logits = jax.nn.one_hot(e, nR, dtype=jnp.float32)
        if (mesh is not None and axis_size > 1 and capacity_factor is None
                and T % axis_size == 0):
            # sharded expert compute: every shard runs the fused kernel on
            # its own tokens (capacity is lossless, so per-shard cursors
            # cannot diverge from the batch-global first-come outcome)
            out = farm_map(
                lambda lg, y: a2a_fused(lg, y, right_fns, cap,
                                        interpret=interpret)[0],
                mesh, axis=axis, in_specs=(P(axis), P(axis)),
                out_specs=P(axis))(logits, ys)
            return out
        out, _keep = a2a_fused(logits, ys, right_fns, cap,
                               interpret=interpret)
        return out

    return batched


# ---------------------------------------------------------------------------
# MoE farm helpers (emitter = learned load balancer)
# ---------------------------------------------------------------------------
def expert_capacity(tokens_per_shard: int, n_experts: int, top_k: int,
                    capacity_factor: float, multiple_of: int = 8) -> int:
    """Slots per expert per token-shard — the bounded SPSC lane depth of the
    MoE farm.  Tasks beyond capacity are dropped (FastFlow would block; a
    synchronous SPMD program must bound the lane)."""
    cap = int(tokens_per_shard * top_k * capacity_factor / n_experts)
    cap = max(multiple_of, (cap + multiple_of - 1) // multiple_of * multiple_of)
    return min(cap, tokens_per_shard)
