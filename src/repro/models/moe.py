"""MoE block — the farm skeleton with a *learned* load balancer.

Paper mapping (Sec. 8.3): the router is an ``ff_loadbalancer`` whose
``selectworker`` is a trained top-k policy; capacity-bounded dispatch is the
bounded SPSC lane (tasks beyond capacity are dropped instead of blocking —
an SPMD program cannot block); the all-to-all is the MPMC network moving
tasks from token shards (producers) to expert shards (consumers); the
combine is the collector weighting worker results; the aux load-balance loss
is the *on-demand scheduling* pressure pushing the emitter towards uniform
lane occupancy.

Two lowerings, chosen per architecture:
  mode='ep'  (E % tp == 0, e.g. kimi-k2 384e/16): experts sharded over the
             model axis; tokens stay sequence-sharded; all-to-all dispatch.
  mode='tp'  (E < tp, e.g. mixtral 8e): experts replicated, expert FFN
             tensor-parallel over the model axis; tokens gathered over the
             model axis for dispatch, outputs reduce-scattered back.

A layer that holds only a share of the experts (``n_experts_held``: one
chip's experts of a deployment that spreads each layer over many) routes
over all ``n_experts`` and computes, for every token, what its own experts
give: each held expert runs on the tokens routed to it, as one group of a
grouped matmul (``kernels/grouped_matmul.py``), and the router's weights
combine them, so no token is dropped.  The absent experts' part is left to
the chips that hold them (it is not computed here), the shared expert is
added once.

The router scores by softmax or, for DeepSeek-V3's ``noaux_tc``, by a
sigmoid with a per-expert correction bias that takes part in picking the
top-k but not in weighting them; the picked scores are normalized and
scaled by ``routed_scale``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.device import expert_capacity
from ..kernels.grouped_matmul import grouped_matmul
from .params import ParamDef


def moe_defs(cfg, layers: Optional[int] = None):
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    E, dff = cfg.n_experts, cfg.moe_d_ff
    ex_ax = "expert" if cfg.moe_mode == "ep" else None
    ff_ax = None if cfg.moe_mode == "ep" else "tp"
    if cfg.n_experts_held:              # this chip's share, all local
        E, ex_ax, ff_ax = cfg.n_experts_held, None, None
    d = {
        "router": ParamDef(lead + (cfg.d_model, cfg.n_experts),
                           la + ("fsdp", None), dtype=jnp.float32),
        "wi": ParamDef(lead + (E, cfg.d_model, dff), la + (ex_ax, "fsdp", ff_ax)),
        "wg": ParamDef(lead + (E, cfg.d_model, dff), la + (ex_ax, "fsdp", ff_ax)),
        "wo": ParamDef(lead + (E, dff, cfg.d_model), la + (ex_ax, ff_ax, "fsdp")),
    }
    if cfg.router_bias:
        d["router_bias"] = ParamDef(lead + (cfg.n_experts,), la + (None,),
                                    dtype=jnp.float32, init="zeros")
    if cfg.n_shared_experts:
        sff = cfg.moe_d_ff * cfg.n_shared_experts
        d["shared"] = {
            "wi": ParamDef(lead + (cfg.d_model, sff), la + ("fsdp", "tp")),
            "wg": ParamDef(lead + (cfg.d_model, sff), la + ("fsdp", "tp")),
            "wo": ParamDef(lead + (sff, cfg.d_model), la + ("tp", "fsdp")),
        }
    return d


def _route(x2d, wr, top_k: int, score: str = "softmax", bias=None,
           scale: float = 1.0):
    """Router: returns (probs(T,E) f32, topk_w(T,K), topk_idx(T,K), aux).
    ``bias`` (E,) is added to the scores to pick the top-k only."""
    logits = x2d.astype(jnp.float32) @ wr.astype(jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    _, topk_idx = lax.top_k(pick, top_k)
    topk_w = jnp.take_along_axis(scores, topk_idx, -1)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        topk_w = topk_w * scale
    # switch-style load-balance loss + router z-loss
    E = probs.shape[-1]
    me = probs.mean(0)                                     # (E,)
    ce_frac = jnp.zeros((E,), jnp.float32).at[topk_idx.reshape(-1)].add(1.0)
    ce_frac = ce_frac / jnp.maximum(topk_idx.size, 1)
    lb = E * jnp.sum(me * ce_frac)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return probs, topk_w, topk_idx, {"lb": lb, "z": z}


def _dispatch_local(x2d, topk_idx, topk_w, E: int, C: int):
    """Capacity-bounded scatter into (E, C, d) + bookkeeping for combine."""
    T, K = topk_idx.shape
    flat_e = topk_idx.reshape(T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # (TK, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    pos = pos.sum(-1) - 1                                      # (TK,)
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)            # overflow slot
    buf = jnp.zeros((E * C + 1, x2d.shape[1]), x2d.dtype)
    xrep = jnp.repeat(x2d, K, axis=0)                          # (TK, d)
    buf = buf.at[slot].add(xrep)
    return buf[:-1].reshape(E, C, -1), slot, keep


def _combine_local(ybuf, slot, keep, topk_w, T: int, K: int):
    yflat = ybuf.reshape(-1, ybuf.shape[-1])
    yflat = jnp.concatenate([yflat, jnp.zeros_like(yflat[:1])], axis=0)
    got = yflat[slot] * keep[:, None]                          # (TK, d)
    got = got.reshape(T, K, -1)
    return jnp.einsum("tkd,tk->td", got.astype(jnp.float32),
                      topk_w.astype(jnp.float32))


def _glu(h, wi, wg, wo):
    a = jnp.einsum("ecd,edf->ecf", h, wi)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg))
    return jnp.einsum("ecf,efd->ecd", a * g, wo)


HELD_WEIGHTS = ("wi", "wg", "wo")


def _held_block(x, p, cfg, plan, active=None, layer=None):
    """The held experts' part for every live token, computed only for the
    (token, held expert) pairs the router picked: the pairs are sorted by
    expert, their rows gathered, each matrix run as one grouped matmul
    whose groups are the experts' pairs (an expert with no pair reads none
    of its weights), and each result added back to its token weighted by
    the router.  No capacity, no drop: the buffer holds T x min(top_k,
    held) rows, every pair a token can have, its tail unused.  ``active``
    (B,) leaves out the rows of dead slots.

    ``p``'s expert matrices are one layer's, (held, ...), or with
    ``layer`` every layer's, (L, held, ...): the kernel then reads layer
    ``layer``'s experts where they lie in the stack (the other layers'
    groups are empty), where a slice of the stack would be a copy.

    Returns (out, lb, z, hits, sizes): ``hits`` (T, held) int32, 1 where a
    live token picked the expert, and ``sizes`` (held,) its column sums,
    the group sizes the matmuls ran with."""
    B, S, d = x.shape
    T, K = B * S, cfg.top_k
    held, off = cfg.n_experts_held, cfg.expert_offset
    R = min(K, held)
    x2 = x.reshape(T, d)
    router = plan.gather_fsdp(p["router"], ("fsdp", None))
    _, tw, ti, aux = _route(x2, router, K, cfg.router_score,
                            p.get("router_bias"), cfg.routed_scale)
    e = ti - off                                          # (T, K)
    mine = (e >= 0) & (e < held)
    if active is not None:
        mine = mine & (jnp.repeat(active, S) > 0)[:, None]
    hits = ((e[:, :, None] == jnp.arange(held)) & mine[:, :, None]).any(1)
    hits = hits.astype(jnp.int32)
    sizes = hits.sum(0)
    # pairs sorted by held expert, the rest last: the first T*R are all
    # the pairs there are
    order = jnp.argsort(jnp.where(mine, e, held).reshape(-1), stable=True)
    xs = x2[order[:T * R] // K]
    if layer is None:
        layer, p = 0, dict(p, **{k: p[k][None] for k in HELD_WEIGHTS})
    L = p["wi"].shape[0]
    groups = lax.dynamic_update_slice(jnp.zeros((L * held,), jnp.int32),
                                      sizes, (layer * held,))

    def stack(name, axes):
        w = plan.gather_fsdp(p[name], (None, None) + axes)
        return w.reshape((L * held,) + w.shape[2:])        # a view
    a = grouped_matmul(xs, stack("wi", ("fsdp", None)), groups)
    g = jax.nn.silu(grouped_matmul(xs, stack("wg", ("fsdp", None)), groups))
    y = grouped_matmul(a * g, stack("wo", (None, "fsdp")), groups)  # (T*R, d)
    # each pair's row in the sorted buffer; a pair not computed reads 0
    row = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32)).reshape(T, K)
    got = jnp.take(y, jnp.where(mine, row, T * R), axis=0, mode="fill",
                   fill_value=0)                          # (T, K, d)
    out = jnp.einsum("tkd,tk->td", got.astype(jnp.float32),
                     jnp.where(mine, tw, 0.0))
    return (out.reshape(B, S, d).astype(x.dtype), aux["lb"], aux["z"],
            hits, sizes)


def moe_block(x, p, cfg, plan, active=None, layer=None):
    """x: (B, S, d) sharded (batch, sp, -). Returns (out, aux) where aux
    holds the router's losses and, for a layer that holds a share of the
    experts, ``moe_hits``: (B*S, n_experts_held) int32, which live tokens
    each held expert took, and ``moe_group_sizes``: (n_experts_held,)
    int32, how many (the rows its matmuls ran on).  ``active`` (B,) marks
    the live rows of a decode step; without it every row is live.  With
    ``layer``, a held share's expert matrices are every layer's stack
    (``_held_block``)."""
    with jax.named_scope("moe"):
        return _moe_block(x, p, cfg, plan, active, layer)


def _moe_block(x, p, cfg, plan, active=None, layer=None):
    if cfg.n_experts_held:
        out, lb, z, hits, sizes = _held_block(x, p, cfg, plan, active,
                                              layer)
        return _add_shared(out, x, p, cfg), {"moe_lb": lb, "moe_z": z,
                                             "moe_hits": hits,
                                             "moe_group_sizes": sizes}
    mesh = plan.mesh
    B, S, _d = x.shape
    batch_ax = plan._fit_dim(B, "batch")
    model_ax = plan.axes("tp")
    E, K = cfg.n_experts, cfg.top_k
    tp = plan.tp
    seq_sharded = (S % tp == 0 and S > 1 and plan.sequence_parallel
                   and model_ax is not None)

    xspec = P(batch_ax, model_ax if seq_sharded else None, None)
    rspec = P(None, None)

    def _pmean(v):
        for ax in (model_ax, batch_ax):
            if ax is not None:
                v = lax.pmean(v, ax)
        return v
    if cfg.moe_mode == "ep":
        wspec = P("model", None, None)
    else:
        wspec = (P(None, None, "model"), P(None, None, "model"),
                 P(None, "model", None))
        wspec_i, wspec_g, wspec_o = wspec

    def route(x2, wr, bias):
        return _route(x2, wr, K, cfg.router_score, bias, cfg.routed_scale)

    def ep_body(xl, wr, bias, wi, wg, wo):
        Bl, Sl, d = xl.shape
        x2 = xl.reshape(Bl * Sl, d)
        probs, tw, ti, aux = route(x2, wr, bias)
        C = expert_capacity(Bl * Sl, E, K, cfg.capacity_factor)
        buf, slot, keep = _dispatch_local(x2, ti, tw, E, C)
        # MPMC: token shards -> expert shards
        buf = lax.all_to_all(buf, model_ax, split_axis=0, concat_axis=1,
                             tiled=True)                  # (E/tp, C*tp, d)
        y = _glu(buf, wi, wg, wo)
        y = lax.all_to_all(y, model_ax, split_axis=1, concat_axis=0,
                           tiled=True)                    # (E, C, d)
        out = _combine_local(y, slot, keep, tw, Bl * Sl, K)
        out = out.reshape(Bl, Sl, d).astype(xl.dtype)
        aux = {k: _pmean(v) for k, v in aux.items()}
        return out, aux["lb"], aux["z"]

    def tp_body(xl, wr, bias, wi, wg, wo):
        # tokens gathered over model axis; expert FFN is ff-sharded
        Bl, Sl, d = xl.shape
        xg = lax.all_gather(xl, model_ax, axis=1, tiled=True) \
            if seq_sharded else xl                              # (B, S, d)
        Sg = xg.shape[1]
        x2 = xg.reshape(Bl * Sg, d)
        probs, tw, ti, aux = route(x2, wr, bias)
        C = expert_capacity(Bl * Sg, E, K, cfg.capacity_factor)
        buf, slot, keep = _dispatch_local(x2, ti, tw, E, C)
        y = _glu(buf, wi, wg, wo)                               # partial (ff shard)
        out = _combine_local(y, slot, keep, tw, Bl * Sg, K)     # partial sums
        out = out.reshape(Bl, Sg, d).astype(xl.dtype)
        # Compose: reduce-scatter partials back to seq shards (or psum)
        if seq_sharded:
            out = lax.psum_scatter(out, model_ax, scatter_dimension=1,
                                   tiled=True)
        elif model_ax is not None:
            out = lax.psum(out, model_ax)
        aux = {k: _pmean(v) for k, v in aux.items()}
        return out, aux["lb"], aux["z"]

    body = ep_body if cfg.moe_mode == "ep" else tp_body
    if cfg.moe_mode == "ep":
        in_specs = (xspec, rspec, P(None), wspec, wspec, wspec)
        wax = ("expert", "fsdp", None)
        oax = ("expert", None, "fsdp")
    else:
        in_specs = (xspec, rspec, P(None), wspec_i, wspec_g, wspec_o)
        wax = (None, "fsdp", "tp")
        oax = (None, "tp", "fsdp")
    # gather the bf16 expert weights over the fsdp axis *before* the
    # shard_map boundary (otherwise GSPMD hoists an f32 convert first and
    # all-gathers 2x the bytes)
    wi = plan.gather_fsdp(p["wi"], wax)
    wg = plan.gather_fsdp(p["wg"], wax)
    wo = plan.gather_fsdp(p["wo"], oax)
    router = plan.gather_fsdp(p["router"], ("fsdp", None))
    fn = shard_map(body, mesh=mesh,
                   in_specs=in_specs,
                   out_specs=(xspec, P(), P()), check_vma=False)
    out, lb, z = fn(x, router, p.get("router_bias"), wi, wg, wo)
    return _add_shared(out, x, p, cfg), {"moe_lb": lb, "moe_z": z}


def _add_shared(out, x, p, cfg):
    if cfg.n_shared_experts:
        sp_ = p["shared"]
        a = jnp.einsum("bsd,df->bsf", x, sp_["wi"])
        g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sp_["wg"]))
        out = out + jnp.einsum("bsf,fd->bsd", a * g, sp_["wo"],
                                preferred_element_type=x.dtype)
    return out
