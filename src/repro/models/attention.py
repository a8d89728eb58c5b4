"""GQA attention with two device-skeleton lowerings.

``parallel='heads'``  map skeleton over heads (Megatron TP): q heads sharded
    over the model axis; KV heads sharded when n_kv % tp == 0, otherwise KV
    projections replicate (they are small) and the KV *cache* shards on
    head_dim.  GQA math is grouped (q reshaped to (kv, group)) — KV is never
    materialized repeated.

``parallel='cp'``     map skeleton over *sequence* (context parallelism) for
    archs whose head count does not divide the TP degree (yi-34b 56H,
    llama3.2 24H, qwen2-vl 12H): queries stay sequence-sharded, KV is
    gathered for the streaming loop.  Decode shards the KV cache on head_dim
    (score/value contractions become partial-sum collectives — the
    farm-with-collector skeleton, flash-decoding).

Both paths use a blocked streaming softmax (never materializing (S, S)).
This XLA path is the only attention path on every backend: the Pallas
kernel (kernels/flash_attention.py) is tested beside it but not wired in.
The q-block x kv-block loops are *unrolled*, so blocks above the causal
diagonal / outside the SWA window are skipped at trace time — both the
FLOPs and the HLO cost analysis reflect kernel-like work.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import apply_mrope, apply_rope
from .params import ParamDef

NEG_INF = -2.0e38


def attn_defs(cfg, layers: Optional[int] = None):
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    hd = cfg.head_dim
    head_ax = "tp" if cfg.attn_parallel == "heads" else None
    kv_ax = head_ax if cfg.n_kv_heads % 16 == 0 else None
    n_q = cfg.padded_heads or cfg.n_heads   # TP-friendly head padding
    return {
        "wq": ParamDef(lead + (cfg.d_model, n_q, hd),
                       la + ("fsdp", head_ax, None)),
        "wk": ParamDef(lead + (cfg.d_model, cfg.n_kv_heads, hd),
                       la + ("fsdp", kv_ax, None)),
        "wv": ParamDef(lead + (cfg.d_model, cfg.n_kv_heads, hd),
                       la + ("fsdp", kv_ax, None)),
        "wo": ParamDef(lead + (n_q, hd, cfg.d_model),
                       la + (head_ax, None, "fsdp")),
    }


def _group(q, n_kv: int):
    """(B, S, H, D) -> (B, S, kv, group, D)."""
    B, S, H, D = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, D)


def _block_mask(qlo, qhi, klo, khi, causal, window):
    qp = jnp.arange(qlo, qhi)[:, None]
    kp = jnp.arange(klo, khi)[None, :]
    m = jnp.zeros((qhi - qlo, khi - klo), jnp.float32)
    if causal:
        m = jnp.where(kp <= qp, m, NEG_INF)
    if window and window > 0:
        m = jnp.where(kp > qp - window, m, NEG_INF)
    return m


def sdpa_streaming(q, k, v, *, causal: bool, window: int = 0,
                   q_block: Optional[int] = 2048, kv_block: int = 2048,
                   q_offset: int = 0, scale: Optional[float] = None):
    """Blocked streaming-softmax grouped attention.

    q: (B, Sq, kv, g, D); k: (B, Sk, kv, D); v: (B, Sk, kv, Dv).
    ``q_block=None`` disables query blocking (context-parallel mode);
    ``scale`` defaults to 1/sqrt(D)."""
    B, Sq, KV, G, D = q.shape
    Dv = v.shape[-1]
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale
    qb = Sq if q_block is None else min(q_block, Sq)
    skip = q_block is not None
    outs = []
    for qlo in range(0, Sq, qb):
        qhi = min(qlo + qb, Sq)
        gqlo, gqhi = qlo + q_offset, qhi + q_offset
        klo, khi = 0, Sk
        if skip:
            if causal:
                khi = min(Sk, gqhi)
            if window and window > 0:
                klo = max(0, gqlo - window + 1)
                klo = (klo // kv_block) * kv_block
        qc = qf[:, qlo:qhi]
        acc = jnp.zeros((B, qhi - qlo, KV, G, Dv), jnp.float32)
        m = jnp.full((B, qhi - qlo, KV, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, qhi - qlo, KV, G), jnp.float32)
        for blo in range(klo, khi, kv_block):
            bhi = min(blo + kv_block, khi)
            kb = k[:, blo:bhi].astype(jnp.float32)
            vb = v[:, blo:bhi].astype(jnp.float32)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qc, kb)
            mask = _block_mask(gqlo, gqhi, blo, bhi, causal, window)
            s = s + mask[None, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd",
                                                     p, vb)
            m = m_new
        outs.append(acc / jnp.maximum(l, 1e-30)[..., None])
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.reshape(B, Sq, KV * G, Dv).astype(q.dtype)


def _cache_axes(cfg):
    """Logical axes of the KV cache (B, n_kv, S_max, hd): shard kv heads
    when they divide the TP degree, else shard head_dim.

    Heads come before positions so that each (slot, head) is one
    contiguous (S_max, hd) matrix: the decode step's score and value
    products then read a layer straight from the stacked cache, where a
    positions-major cache costs a relayout copy of the layer every step."""
    if cfg.attn_parallel == "heads" and cfg.n_kv_heads % 16 == 0:
        return ("batch", "tp", None, None)
    return ("batch", None, None, "tp")


def _write_rows(stack, new, layer, cache_pos):
    """Store the step's rows ``new`` (B, 1, kv, hd) at ``(layer, b, :,
    cache_pos[b])`` of the stacked cache (L, B, kv, S, hd): one write of
    B x kv rows, in place when the stack is a donated buffer or a loop
    carry.  Positions past the end clamp to the last row, as a dynamic
    update slice does."""
    new = new.astype(stack.dtype)
    B, _, n_kv, _ = new.shape
    if getattr(cache_pos, "ndim", 0) == 1:
        b = jnp.arange(B)[:, None]
        h = jnp.arange(n_kv)[None, :]
        return stack.at[layer, b, h, cache_pos[:, None]].set(new[:, 0],
                                                              mode="clip")
    return jax.lax.dynamic_update_slice(
        stack, new.transpose(0, 2, 1, 3)[None], (layer, 0, 0, cache_pos, 0))


def attention(x, p, cfg, plan, *, positions, causal=True, window=0,
              cache=None, cache_pos=None, mrope_positions=None, layer=None,
              q_block: int = 2048, kv_block: int = 2048):
    """Attention block: projections + blocked grouped SDPA + output proj.

    train:    cache=None          -> (out, None)
    prefill:  cache='init'        -> (out, {k, v} padded to cfg.cache_len)
    decode:   cache={k, v} dict   -> (out, updated cache); x is (B, 1, d),
              the cache is stacked (L, B, kv, S_max, hd) and ``layer`` this
              block's index in it, ``cache_pos`` the write slot (scalar or
              (B,)), ``positions`` (B, 1) global positions.  Only the B new
              rows are written; attention reads layer ``layer`` in place.
    """
    B, S, _ = x.shape
    n_kv = cfg.n_kv_heads
    decode = isinstance(cache, dict)

    if cfg.attn_parallel == "heads" and not decode:
        # SP boundary: gather bf16 activations over seq shards here
        x = plan.constrain(x, "batch", None, None)
    head_ax = "tp" if cfg.attn_parallel == "heads" else None
    kv_ax = head_ax if cfg.n_kv_heads % 16 == 0 else None
    wq = plan.gather_fsdp(p["wq"], ("fsdp", head_ax, None))
    wk = plan.gather_fsdp(p["wk"], ("fsdp", kv_ax, None))
    wv = plan.gather_fsdp(p["wv"], ("fsdp", kv_ax, None))
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)

    if mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta)
    elif cfg.use_rope:
        pos2d = positions if positions.ndim == 2 else \
            jnp.broadcast_to(positions[None, :], (B, S))
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)

    if cfg.attn_parallel == "heads":
        q = plan.constrain(q, "batch", None, "tp", None)
        k = plan.constrain(k, "batch", None, "tp", None)
        v = plan.constrain(v, "batch", None, "tp", None)
    else:
        q = plan.constrain(q, "batch", "cp", None, None)

    if decode:
        ca = ("layers",) + _cache_axes(cfg)
        sk = plan.constrain(_write_rows(cache["k"], k, layer, cache_pos), *ca)
        sv = plan.constrain(_write_rows(cache["v"], v, layer, cache_pos), *ca)
        new_cache = {"k": sk, "v": sv}
        ck = jax.lax.dynamic_index_in_dim(sk, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(sv, layer, 0, keepdims=False)
        Sk = ck.shape[2]
        k_pos = jnp.arange(Sk)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        qg = _group(q, n_kv).astype(jnp.float32) * scale   # (B,1,kv,g,D)
        s = jnp.einsum("bqhgd,bhkd->bqhgk", qg, ck.astype(jnp.float32))
        ring = window > 0 and Sk == window
        valid = k_pos[None, None, :] <= positions[:, :, None]
        if window and window > 0 and not ring:
            valid &= k_pos[None, None, :] > (positions[:, :, None] - window)
        if ring:
            # warm ring buffer: every slot holds an in-window entry; the
            # k_pos<=pos test is only exact during warmup (pos < window)
            valid = valid | (positions[:, :, None] >= window)
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bqhgk,bhkd->bqhgd", w, cv.astype(jnp.float32))
        out = out.reshape(B, S, q.shape[2], cfg.head_dim).astype(x.dtype)
    else:
        if cfg.attn_parallel == "cp":
            k = plan.constrain(k, "batch", None, None, None)
            v = plan.constrain(v, "batch", None, None, None)
            qb = None
        else:
            qb = q_block
        out = sdpa_streaming(_group(q, n_kv), k, v, causal=causal,
                             window=window, q_block=qb, kv_block=kv_block)
        new_cache = None
        if cache == "init":
            ca = _cache_axes(cfg)
            ck, cv = k, v
            tgt = getattr(cfg, "cache_len", None) or S
            if cfg.attn_kind == "swa" and tgt == window and S > window:
                shift = S % window
                ck = jnp.roll(ck[:, -window:], shift, axis=1)
                cv = jnp.roll(cv[:, -window:], shift, axis=1)
            elif tgt > S:
                pad = [(0, 0), (0, tgt - S), (0, 0), (0, 0)]
                ck = jnp.pad(ck, pad)
                cv = jnp.pad(cv, pad)
            new_cache = {"k": plan.constrain(ck.transpose(0, 2, 1, 3), *ca),
                         "v": plan.constrain(cv.transpose(0, 2, 1, 3), *ca)}

    head_ax2 = "tp" if cfg.attn_parallel == "heads" else None
    wo = plan.gather_fsdp(p["wo"], (head_ax2, None, "fsdp"))
    o = jnp.einsum("bshk,hkd->bsd", out, wo,
                   preferred_element_type=jnp.bfloat16)
    if not decode:
        o = plan.constrain(o, "batch", "sp", None)
    return o, new_cache


def cross_attention(x, p, enc_kv, cfg, plan, kv_block: int = 2048):
    """Encoder-decoder cross attention (whisper): q from decoder x, kv
    precomputed from the encoder output (cached at prefill)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.attn_parallel == "heads":
        q = plan.constrain(q, "batch", None, "tp", None)
    out = sdpa_streaming(_group(q, cfg.n_kv_heads), enc_kv["k"], enc_kv["v"],
                         causal=False, window=0, q_block=2048,
                         kv_block=kv_block)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_kv(enc_out, p, cfg, plan):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.attn_parallel == "heads":
        k = plan.constrain(k, "batch", None, "tp", None)
        v = plan.constrain(v, "batch", None, "tp", None)
    return {"k": k, "v": v}
