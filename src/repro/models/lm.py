"""Unified language-model backbone covering all assigned architectures.

A model is a sequence of *segments*, each a homogeneous stack of blocks run
under ``lax.scan`` (stacked params, full remat).  Block kinds:

  dense        GQA attention + GLU MLP           (gemma/yi/mistral/llama/...)
  moe          GQA attention + MoE farm          (kimi, mixtral)
  mamba2       SSD state-space block             (zamba2 backbone)
  mlstm/slstm  xLSTM blocks                      (xlstm-125m)
  shared_attn  zamba2's shared transformer block (same params every call —
               the broadcast/MISD farm: one task stream, one worker reused)

With ``attn_kind="mla"`` dense and moe blocks take latent attention
(models/mla.py) in place of GQA and cache one latent row per position
(kimi-k2: a leading dense layer, then MoE layers).

Families 'encdec' (whisper) and 'vlm' (qwen2-vl) reuse the same machinery
with stub frontends (precomputed frame/patch embeddings per the assignment).

Sharding: only logical axes (core/plan.py).  Embedding and cross-entropy are
vocab-parallel (Megatron-style shard_map) so full logits are never
materialized.  Decode carries each stacked cache through the layer scan:
each layer stores its new K/V rows with one batched scatter into the
carried cache, which the TPU compiler emits as an in-place fusion, not a
copy or a loop (tests/test_tpu_compile.py guards this on a compiled
decode step).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .attention import attention, attn_defs, cross_attention, cross_kv
from .layers import apply_norm, mlp, mlp_defs, norm_defs
from .mla import CACHE_AXES as MLA_CACHE_AXES
from .mla import cache_width, mla_attention, mla_defs
from .moe import HELD_WEIGHTS, moe_block, moe_defs
from .params import ParamDef, init_params, shape_structs
from .ssm import mamba2_block, mamba2_defs, mamba2_state_defs
from .xlstm import (mlstm_block, mlstm_defs, mlstm_state_defs, slstm_block,
                    slstm_defs, slstm_state_defs)


# ---------------------------------------------------------------------------
# vocab-parallel embedding / cross entropy
# ---------------------------------------------------------------------------
def vocab_parallel_embed(tokens, emb, plan):
    mesh = plan.mesh
    b_ax, m_ax = plan.axes("batch"), plan.axes("tp")
    if m_ax is None:
        return emb[tokens].astype(jnp.bfloat16)
    b_ax = plan._fit_dim(tokens.shape[0], "batch")
    tp = plan.tp
    V = emb.shape[0]
    Vl = V // tp
    S = tokens.shape[1]
    seq_scatter = (S % tp == 0) and plan.sequence_parallel

    def body(tok, emb_l):
        idx = lax.axis_index(m_ax)
        loc = tok - idx * Vl
        ok = (loc >= 0) & (loc < Vl)
        e = emb_l[jnp.clip(loc, 0, Vl - 1)] * ok[..., None].astype(emb_l.dtype)
        e = e.astype(jnp.bfloat16)
        if seq_scatter:
            return lax.psum_scatter(e, m_ax, scatter_dimension=1, tiled=True)
        return lax.psum(e, m_ax)

    out_spec = P(b_ax, m_ax if seq_scatter else None, None)
    return shard_map(body, mesh=mesh,
                     in_specs=(P(b_ax, None), P("model", None)),
                     out_specs=out_spec, check_vma=False)(tokens, emb)


def vocab_parallel_ce(x, unemb, labels, mask, plan, chunks: int = 1):
    """Mean CE over masked tokens; logits never materialized beyond a
    (B_loc, S/chunks, V/tp) fp32 tile.  x: (B,S,d) seq-sharded; labels (B,S)."""
    mesh = plan.mesh
    b_ax, m_ax = plan.axes("batch"), plan.axes("tp")
    if m_ax is None:
        logits = jnp.einsum("bsd,dv->bsv", x, unemb).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        lab = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        nll = (lse - lab) * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    tp = plan.tp
    b_ax = plan._fit_dim(x.shape[0], "batch")
    V = unemb.shape[1]
    Vl = V // tp

    def body(xl, w_l, lab, msk):
        # xl: (B_loc, S or S/tp, d) — gather seq if sp-sharded
        if xl.shape[1] != lab.shape[1]:
            xl = lax.all_gather(xl, m_ax, axis=1, tiled=True)
        idx = lax.axis_index(m_ax)
        lo = idx * Vl
        S = xl.shape[1]
        cs = max(1, S // max(chunks, 1))
        nll_parts = []
        for c0 in range(0, S, cs):
            xc = xl[:, c0:c0 + cs]
            lc = lab[:, c0:c0 + cs]
            lg = jnp.einsum("bsd,dv->bsv", xc, w_l).astype(jnp.float32)
            # stop-grad on the max: exact (lse is shift-invariant) and pmax
            # has no transpose rule
            mx = lax.pmax(jax.lax.stop_gradient(jnp.max(lg, -1)), m_ax)
            ssum = lax.psum(jnp.sum(jnp.exp(lg - mx[..., None]), -1), m_ax)
            lse = jnp.log(ssum) + mx
            loc = lc - lo
            ok = (loc >= 0) & (loc < Vl)
            ll = jnp.take_along_axis(lg, jnp.clip(loc, 0, Vl - 1)[..., None],
                                     -1)[..., 0]
            ll = lax.psum(ll * ok.astype(jnp.float32), m_ax)
            nll_parts.append(lse - ll)
        nll = jnp.concatenate(nll_parts, axis=1) if len(nll_parts) > 1 \
            else nll_parts[0]
        loss = jnp.sum(nll * msk)
        cnt = jnp.sum(msk)
        return lax.pmean(loss, b_ax), lax.pmean(cnt, b_ax)

    x_seq_ax = m_ax if (plan.sequence_parallel
                        and x.shape[1] % tp == 0) else None
    loss, cnt = shard_map(
        body, mesh=mesh,
        in_specs=(P(b_ax, x_seq_ax, None), P(None, "model"),
                  P(b_ax, None), P(b_ax, None)),
        out_specs=(P(), P()), check_vma=False)(x, unemb, labels, mask)
    return loss / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------
def _residual(x, delta, plan):
    return plan.constrain(x + delta, "batch", "sp", None)


def dense_block(x, p, cfg, plan, *, mode, cache=None, positions=None,
                pos_offset=0, mrope_positions=None, causal=True,
                window=0, moe=False, layer=None, active=None):
    aux = {}
    xn = apply_norm(x, p["ln1"], cfg.norm)
    if cfg.attn_kind == "mla":
        a, new_cache = mla_attention(
            xn, p["attn"], cfg, plan, positions=positions, cache=cache,
            cache_pos=pos_offset, layer=layer, q_block=cfg.q_block,
            kv_block=cfg.kv_block)
    else:
        a, new_cache = attention(
            xn, p["attn"], cfg, plan, positions=positions, causal=causal,
            window=window, cache=cache, cache_pos=pos_offset, layer=layer,
            mrope_positions=mrope_positions,
            q_block=cfg.q_block, kv_block=cfg.kv_block)
    x = _residual(x, a, plan)
    xn = apply_norm(x, p["ln2"], cfg.norm)
    if moe:
        m, aux = moe_block(xn, p["moe"], cfg, plan, active, layer)
    else:
        m = mlp(xn, p["mlp"], cfg.act, plan)
    x = _residual(x, m, plan)
    return x, new_cache, aux


def dense_defs(cfg, layers, moe=False, kind_cfg=None):
    d = {
        "ln1": norm_defs(cfg.d_model, cfg.norm, layers),
        "ln2": norm_defs(cfg.d_model, cfg.norm, layers),
        "attn": (mla_defs if cfg.attn_kind == "mla" else attn_defs)(
            cfg, layers),
    }
    if moe:
        d["moe"] = moe_defs(cfg, layers)
    else:
        d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, layers)
    return d


def _layer_of(stack, layer):
    """One layer of a stacked cache tree, read in place."""
    return jax.tree.map(
        lambda t: lax.dynamic_index_in_dim(t, layer, 0, keepdims=False), stack)


def apply_block(kind, x, p, cfg, plan, *, mode, cache=None, positions=None,
                pos_offset=0, mrope_positions=None, enc_out=None, layer=None,
                active=None):
    """Uniform block dispatch. Returns (x, new_cache, aux).

    In decode ``cache`` is the kind's whole stacked cache and ``layer`` the
    block's index in it; the stack comes back with only this layer's new
    entries written: the new K/V rows of an append-only cache, the whole
    (small) state of a recurrent block, nothing of a read-only cross cache.
    ``active`` (B,) marks the live rows, for an expert layer that holds a
    share of the experts.
    """
    window = cfg.window if cfg.attn_kind == "swa" else 0
    if kind in ("dense", "moe"):
        return dense_block(x, p, cfg, plan, mode=mode, cache=cache,
                           positions=positions, pos_offset=pos_offset,
                           mrope_positions=mrope_positions, layer=layer,
                           causal=True, window=window, moe=(kind == "moe"),
                           active=active)
    if kind == "shared_attn":
        return dense_block(x, p, cfg, plan, mode=mode, cache=cache,
                           positions=positions, pos_offset=pos_offset,
                           layer=layer, causal=True,
                           window=cfg.shared_attn_window)
    if kind == "enc":
        return dense_block(x, p, cfg, plan, mode=mode, cache=None,
                           positions=positions, causal=False, window=0)
    if kind == "dec":
        aux = {}
        decode = isinstance(cache, dict)
        xn = apply_norm(x, p["ln1"], cfg.norm)
        a, new_self = attention(xn, p["attn"], cfg, plan, positions=positions,
                                causal=True, window=0, cache=(
                                    cache["self"] if decode else cache),
                                cache_pos=pos_offset, layer=layer,
                                q_block=cfg.q_block, kv_block=cfg.kv_block)
        x = _residual(x, a, plan)
        xn = apply_norm(x, p["ln_x"], cfg.norm)
        if decode:                          # cached cross-kv, read only
            ckv = _layer_of(cache["cross"], layer)
        else:
            ckv = cross_kv(enc_out, p["xattn"], cfg, plan)
        ca = cross_attention(xn, p["xattn"], ckv, cfg, plan)
        x = _residual(x, ca, plan)
        xn = apply_norm(x, p["ln2"], cfg.norm)
        x = _residual(x, mlp(xn, p["mlp"], cfg.act, plan), plan)
        new_cache = None
        if decode:
            new_cache = {"self": new_self, "cross": cache["cross"]}
        elif cache is not None:
            new_cache = {"self": new_self, "cross": ckv}
        return x, new_cache, aux
    if kind in ("mamba2", "mlstm", "slstm"):
        decode = isinstance(cache, dict)
        state = _layer_of(cache, layer) if decode else cache
        if kind == "mamba2":
            x, st = mamba2_block(x, p, cfg, plan, state=state,
                                 chunk=cfg.gla_chunk)
        elif kind == "mlstm":
            x, st = mlstm_block(x, p, cfg, plan, state=state,
                                chunk=cfg.gla_chunk)
        else:
            x, st = slstm_block(x, p, cfg, plan, state=state)
        if decode:                          # the new state replaces the old
            st = jax.tree.map(
                lambda t, s: lax.dynamic_update_index_in_dim(t, s, layer, 0),
                cache, st)
        return x, st, {}
    raise ValueError(kind)


def block_defs(kind, cfg, layers):
    if kind == "dense":
        return dense_defs(cfg, layers)
    if kind == "moe":
        return dense_defs(cfg, layers, moe=True)
    if kind in ("shared_attn", "enc"):
        return dense_defs(cfg, layers)
    if kind == "dec":
        return {
            "ln1": norm_defs(cfg.d_model, cfg.norm, layers),
            "ln_x": norm_defs(cfg.d_model, cfg.norm, layers),
            "ln2": norm_defs(cfg.d_model, cfg.norm, layers),
            "attn": attn_defs(cfg, layers),
            "xattn": attn_defs(cfg, layers),
            "mlp": mlp_defs(cfg.d_model, cfg.d_ff, layers),
        }
    if kind == "mamba2":
        return mamba2_defs(cfg, layers)
    if kind == "mlstm":
        return mlstm_defs(cfg, layers)
    if kind == "slstm":
        return slstm_defs(cfg, layers)
    raise ValueError(kind)


def _cache_struct(kind, cfg, B, S_max, layers):
    """(shape, dtype, axes) templates for one stack's decode cache."""
    if kind in ("dense", "moe") and cfg.attn_kind == "mla":
        return {"ckv": ((layers, B, S_max, cache_width(cfg)), jnp.bfloat16,
                        MLA_CACHE_AXES)}
    if kind in ("dense", "moe", "shared_attn", "enc", "dec"):
        from .attention import _cache_axes
        ca = ("layers",) + _cache_axes(cfg)
        def kvd(shape, axes):
            return {"k": (shape, jnp.bfloat16, axes),
                    "v": (shape, jnp.bfloat16, axes)}
        kv = kvd((layers, B, cfg.n_kv_heads, S_max, cfg.head_dim), ca)
        if kind == "dec":
            # the cross cache keeps cross_kv's positions-major layout
            cross = kvd((layers, B, cfg.enc_len, cfg.n_kv_heads,
                         cfg.head_dim), ca[:2] + (ca[3], ca[2], ca[4]))
            return {"self": kv, "cross": cross}
        return kv
    if kind == "mamba2":
        return mamba2_state_defs(cfg, B, layers)
    if kind == "mlstm":
        return mlstm_state_defs(cfg, B, layers)
    if kind == "slstm":
        return slstm_state_defs(cfg, B, layers)
    raise ValueError(kind)


# what a step reports of its expert layers (``moe_block``), per layer
STEP_STATS = ("moe_hits", "moe_group_sizes")


def _step_stats(aux) -> Dict[str, Any]:
    return {k: aux[k] for k in STEP_STATS if k in aux}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
class LM:
    def __init__(self, cfg):
        self.cfg = cfg

    # -- parameters -----------------------------------------------------------
    def param_defs(self):
        cfg = self.cfg
        d: Dict[str, Any] = {
            "embed": {"emb": ParamDef((cfg.vocab, cfg.d_model),
                                      ("tp", "fsdp"), init="embed",
                                      scale=0.02)},
            "final_norm": norm_defs(cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            d["embed"]["unemb"] = ParamDef((cfg.d_model, cfg.vocab),
                                           ("fsdp", "tp"))
        stacks = {}
        for kind, total in cfg.stack_sizes().items():
            if kind == "shared_attn":
                d["shared"] = block_defs("shared_attn", cfg, None)
            else:
                stacks[kind] = block_defs(kind, cfg, total)
        d["stacks"] = stacks
        if cfg.family == "encdec":
            d["enc_norm"] = norm_defs(cfg.d_model, cfg.norm)
        return d

    def init(self, key):
        return init_params(self.param_defs(), key)

    # -- segment runner ---------------------------------------------------------
    def _run_segments(self, params, x, *, mode, caches=None, positions=None,
                      pos_offset=0, mrope_positions=None, enc_out=None,
                      segments=None, active=None):
        """Run the segment list; returns (x, new_caches, aux_sum).

        Decode carries each kind's whole stacked cache through its layer
        scan (and from one segment of the kind to the next), with the
        layer's index in the stack as the scanned input, so every block
        writes its new entries into the donated buffer in place.

        In prefill and decode, an expert layer that holds a share of the
        experts reports which live tokens each held expert took: ``aux_sum``
        then holds ``moe_hits``, (MoE layers, tokens, held) int32, and
        ``moe_group_sizes``, (MoE layers, held) int32, its sums over the
        tokens.  ``active`` (B,) marks a decode step's live rows."""
        cfg = self.cfg
        segments = segments if segments is not None else cfg.segments
        offsets = {k: 0 for k, _ in segments}
        shared_i = 0
        aux_tot = {}
        new_caches: Dict[str, Any] = {}
        stats = []
        if mode == "decode":
            caches = dict(caches)

        def add_aux(a):
            for k, v in a.items():
                aux_tot[k] = aux_tot.get(k, 0.0) + v

        for kind, count in segments:
            if kind == "shared_attn":
                p = params["shared"]
                if mode == "train":
                    blk = jax.checkpoint(
                        lambda xx, pp: apply_block(
                            "shared_attn", xx, pp, cfg, self._plan, mode=mode,
                            cache=None, positions=positions,
                            pos_offset=pos_offset)[0])
                    x = blk(x, p)
                elif mode == "prefill":
                    x, nc, _ = apply_block(
                        "shared_attn", x, p, cfg, self._plan, mode=mode,
                        cache="init", positions=positions,
                        pos_offset=pos_offset)
                    new_caches.setdefault("shared_attn", []).append(nc)
                else:
                    x, caches[kind], _ = apply_block(
                        "shared_attn", x, p, cfg, self._plan, mode=mode,
                        cache=caches[kind], positions=positions,
                        pos_offset=pos_offset, layer=shared_i)
                shared_i += 1
                continue

            start = offsets[kind]
            offsets[kind] = start + count
            stack = jax.tree.map(lambda t: t[start:start + count],
                                 params["stacks"][kind])
            plan = self._plan
            # a held expert share's matrices stay whole: each layer's block
            # gets the kind's stack and its index in it, for the grouped
            # matmul to read them in place (a layer's slice would be copied)
            experts = None
            if kind == "moe" and cfg.n_experts_held and mode != "train":
                experts = {k: params["stacks"][kind]["moe"][k]
                           for k in HELD_WEIGHTS}
                stack = dict(stack, moe={k: v for k, v in stack["moe"].items()
                                         if k not in experts})

            def whole(pl, _experts=experts):
                if _experts is None:
                    return pl
                return dict(pl, moe=dict(pl["moe"], **_experts))

            if mode == "train":
                def body(carry, pl, _kind=kind):
                    xx, aux_c = carry
                    def blk(xx, pl):
                        y, _, aux = apply_block(
                            _kind, xx, pl, cfg, plan, mode="train",
                            positions=positions,
                            mrope_positions=mrope_positions, enc_out=enc_out)
                        return y, aux
                    y, aux = jax.checkpoint(blk)(xx, pl)
                    aux_c = {k: v + aux[k] for k, v in aux_c.items()}
                    return (y, aux_c), None
                aux0 = {"moe_lb": jnp.zeros((), jnp.float32),
                        "moe_z": jnp.zeros((), jnp.float32)} \
                    if kind == "moe" else {}
                (x, aux_c), _ = lax.scan(body, (x, aux0), stack)
                add_aux(aux_c)
            elif mode == "prefill":
                def body(xx, pi, _kind=kind):
                    pl, li = pi
                    y, nc, aux = apply_block(
                        _kind, xx, whole(pl), cfg, plan, mode="prefill",
                        cache="init", positions=positions,
                        mrope_positions=mrope_positions, enc_out=enc_out,
                        layer=li)
                    return y, (nc, _step_stats(aux))
                layer_ids = (start + jnp.arange(count, dtype=jnp.int32)
                             if experts else None)
                x, (ncs, st) = lax.scan(body, x, (stack, layer_ids))
                new_caches.setdefault(kind, []).append(ncs)
                stats.append(st)
            else:  # decode
                def body(carry, pi, _kind=kind):
                    xx, cs = carry
                    pl, li = pi
                    y, cs, aux = apply_block(
                        _kind, xx, whole(pl), cfg, plan, mode="decode",
                        cache=cs, positions=positions, pos_offset=pos_offset,
                        mrope_positions=mrope_positions, enc_out=enc_out,
                        layer=li, active=active)
                    return (y, cs), _step_stats(aux)
                layer_ids = start + jnp.arange(count, dtype=jnp.int32)
                (x, caches[kind]), st = lax.scan(body, (x, caches[kind]),
                                                 (stack, layer_ids))
                stats.append(st)

        for k in STEP_STATS:
            per_seg = [st[k] for st in stats if k in st]
            if per_seg:
                aux_tot[k] = jnp.concatenate(per_seg, 0)
        if mode == "decode":
            return x, caches, aux_tot
        # prefill: concatenate per-kind cache pieces into full stacks
        out_caches = {}
        for kind, pieces in new_caches.items():
            if kind == "shared_attn":
                out_caches[kind] = jax.tree.map(
                    lambda *ts: jnp.stack(ts, 0), *pieces) \
                    if len(pieces) > 1 else jax.tree.map(
                        lambda t: t[None], pieces[0])
            else:
                out_caches[kind] = jax.tree.map(
                    lambda *ts: jnp.concatenate(ts, 0), *pieces) \
                    if len(pieces) > 1 else pieces[0]
        return x, out_caches, aux_tot

    # -- entry points -------------------------------------------------------------
    def _embed_in(self, params, batch, plan):
        cfg = self.cfg
        if cfg.family == "vlm" and "embeds" in batch:
            x = batch["embeds"].astype(jnp.bfloat16)
            x = plan.constrain(x, "batch", "sp", None)
        else:
            x = vocab_parallel_embed(batch["tokens"], params["embed"]["emb"],
                                     plan)
            x = plan.constrain(x, "batch", "sp", None)
        return x

    def loss(self, params, batch, plan):
        cfg = self.cfg
        self._plan = plan
        if cfg.family == "encdec":
            return self._encdec_loss(params, batch, plan)
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed_in(params, batch, plan)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        mrope = batch.get("mrope_positions") if cfg.mrope else None
        x, _, aux = self._run_segments(params, x, mode="train",
                                       positions=positions,
                                       mrope_positions=mrope)
        x = apply_norm(x, params["final_norm"], cfg.norm)
        labels = jnp.roll(tokens, -1, axis=1)
        mask = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
        unemb = params["embed"].get("unemb")
        if unemb is None:
            unemb = params["embed"]["emb"].T
        loss = vocab_parallel_ce(x, unemb, labels, mask, plan,
                                 chunks=cfg.loss_chunks)
        metrics = {"ce": loss}
        if aux:
            loss = loss + 0.01 * aux.get("moe_lb", 0.0) \
                + 0.001 * aux.get("moe_z", 0.0)
            metrics.update(aux)
        return loss, metrics

    def _encdec_loss(self, params, batch, plan):
        cfg = self.cfg
        frames = batch["frames"].astype(jnp.bfloat16)   # (B, S_enc, d) stub
        tokens = batch["tokens"]                        # (B, S_dec)
        B, S_dec = tokens.shape
        enc_x = plan.constrain(frames, "batch", "sp", None)
        pos_e = jnp.broadcast_to(jnp.arange(enc_x.shape[1])[None],
                                 (B, enc_x.shape[1]))
        enc_x, _, _ = self._run_segments(
            params, enc_x, mode="train", positions=pos_e,
            segments=[("enc", cfg.enc_layers)])
        enc_out = apply_norm(enc_x, params["enc_norm"], cfg.norm)
        x = vocab_parallel_embed(tokens, params["embed"]["emb"], plan)
        x = plan.constrain(x, "batch", "sp", None)
        pos_d = jnp.broadcast_to(jnp.arange(S_dec)[None], (B, S_dec))
        x, _, _ = self._run_segments(
            params, x, mode="train", positions=pos_d, enc_out=enc_out,
            segments=[("dec", cfg.dec_layers)])
        x = apply_norm(x, params["final_norm"], cfg.norm)
        labels = jnp.roll(tokens, -1, axis=1)
        mask = jnp.ones((B, S_dec), jnp.float32).at[:, -1].set(0.0)
        unemb = params["embed"].get("unemb")
        if unemb is None:
            unemb = params["embed"]["emb"].T
        loss = vocab_parallel_ce(x, unemb, labels, mask, plan,
                                 chunks=cfg.loss_chunks)
        return loss, {"ce": loss}

    # -- serving -----------------------------------------------------------------
    def prefill(self, params, batch, plan, cache_len: Optional[int] = None,
                stats: bool = False):
        """Process the prompt; returns (last-position logits (B,1,V) vocab-
        sharded, caches padded to ``cache_len``), and with ``stats`` a dict
        of the step's counts as well (``moe_hits``, ``moe_group_sizes``, see
        _run_segments)."""
        cfg = self.cfg
        self._plan = plan
        if cache_len is not None:
            cfg.cache_len = (min(cache_len, cfg.window)
                             if cfg.attn_kind == "swa" else cache_len)
        if cfg.family == "encdec":
            return self._encdec_prefill(params, batch, plan)
        tokens = batch.get("tokens")
        if cfg.family == "vlm" and "embeds" in batch:
            x = batch["embeds"].astype(jnp.bfloat16)
            B, S = x.shape[:2]
            x = plan.constrain(x, "batch", "sp", None)
        else:
            B, S = tokens.shape
            x = self._embed_in(params, batch, plan)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        mrope = batch.get("mrope_positions") if cfg.mrope else None
        x, caches, aux = self._run_segments(params, x, mode="prefill",
                                            positions=positions,
                                            mrope_positions=mrope)
        x = apply_norm(x, params["final_norm"], cfg.norm)
        x_last = x[:, -1:]
        unemb = params["embed"].get("unemb")
        if unemb is None:
            unemb = params["embed"]["emb"].T
        logits = jnp.einsum("bsd,dv->bsv", x_last, unemb)
        logits = plan.constrain(logits, "batch", None, "tp")
        if stats:
            return logits, caches, _step_stats(aux)
        return logits, caches

    def _encdec_prefill(self, params, batch, plan):
        cfg = self.cfg
        frames = batch["frames"].astype(jnp.bfloat16)
        tokens = batch["tokens"]
        B, S_dec = tokens.shape
        enc_x = plan.constrain(frames, "batch", "sp", None)
        pos_e = jnp.broadcast_to(jnp.arange(enc_x.shape[1])[None],
                                 (B, enc_x.shape[1]))
        enc_x, _, _ = self._run_segments(
            params, enc_x, mode="prefill", positions=pos_e,
            segments=[("enc", cfg.enc_layers)])
        enc_out = apply_norm(enc_x, params["enc_norm"], cfg.norm)
        x = vocab_parallel_embed(tokens, params["embed"]["emb"], plan)
        x = plan.constrain(x, "batch", "sp", None)
        pos_d = jnp.broadcast_to(jnp.arange(S_dec)[None], (B, S_dec))
        x, caches, _ = self._run_segments(
            params, x, mode="prefill", positions=pos_d, enc_out=enc_out,
            segments=[("dec", cfg.dec_layers)])
        x = apply_norm(x, params["final_norm"], cfg.norm)
        unemb = params["embed"].get("unemb")
        if unemb is None:
            unemb = params["embed"]["emb"].T
        logits = jnp.einsum("bsd,dv->bsv", x[:, -1:], unemb)
        return plan.constrain(logits, "batch", None, "tp"), caches

    def decode_step(self, params, caches, batch, plan, stats: bool = False):
        """One token for every sequence.  batch: {'token': (B,1), 'pos': ()
        or (B,)}, and optionally 'active': (B,) int32, the live slots (the
        rest are left out of the held experts' work and counts).  Returns
        (logits (B,1,V) vocab-sharded, new caches), and with ``stats`` a
        dict of the step's counts as well (``moe_hits``,
        ``moe_group_sizes``, see _run_segments)."""
        cfg = self.cfg
        self._plan = plan
        tok = batch["token"]
        B = tok.shape[0]
        pos = batch["pos"]
        if getattr(pos, "ndim", 0) == 1:      # per-sequence positions
            positions = pos[:, None].astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(pos[None, None],
                                         (B, 1)).astype(jnp.int32)
        if cfg.family == "vlm" and "embeds" in batch:
            x = batch["embeds"].astype(jnp.bfloat16)
        else:
            x = vocab_parallel_embed(tok, params["embed"]["emb"], plan)
        mrope = batch.get("mrope_positions") if cfg.mrope else None
        cache_pos = self._cache_write_pos(pos)
        segs = [("dec", cfg.dec_layers)] if cfg.family == "encdec" else None
        x, new_caches, aux = self._run_segments(
            params, x, mode="decode", caches=caches, positions=positions,
            pos_offset=cache_pos, mrope_positions=mrope, segments=segs,
            active=batch.get("active"))
        x = apply_norm(x, params["final_norm"], cfg.norm)
        unemb = params["embed"].get("unemb")
        if unemb is None:
            unemb = params["embed"]["emb"].T
        logits = jnp.einsum("bsd,dv->bsv", x, unemb)
        logits = plan.constrain(logits, "batch", None, "tp")
        if stats:
            return logits, new_caches, _step_stats(aux)
        return logits, new_caches

    def _cache_write_pos(self, pos):
        cfg = self.cfg
        if cfg.attn_kind == "swa" and cfg.cache_len == cfg.window:
            return jnp.mod(pos, cfg.window)
        return pos

    def cache_defs(self, B: int, S_max: int):
        """Tree of (shape, dtype, axes) for the decode caches."""
        cfg = self.cfg
        S_eff = min(S_max, cfg.window) if cfg.attn_kind == "swa" else S_max
        cfg.cache_len = S_eff
        out = {}
        for kind, total in cfg.stack_sizes().items():
            L = total
            if cfg.family == "encdec" and kind == "enc":
                continue
            out[kind] = _cache_struct(kind, cfg, B,
                                      S_eff if kind != "dec" else S_eff, L)
        return out
