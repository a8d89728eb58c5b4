"""Multi-head latent attention (MLA, DeepSeek-V2/V3, Kimi-K2).

Queries and keys/values go through low-rank projections.  Each head's
query and key split into a part without positions (``qk_nope_head_dim``)
and a rotary part (``qk_rope_head_dim``); the rotary key is one vector
shared by every head.  Keys and values without positions are decompressed
per head from a normalized latent ``c_kv`` (``kv_lora_rank`` wide).

Two lowerings of the same attention:

- prefill and training decompress K and V per head and run the blocked
  streaming softmax of ``attention.sdpa_streaming``;
- decode absorbs ``W_UK`` into the query and ``W_UV`` into the output, and
  attends over the cache itself: for each position, ``c_kv`` (after its
  RMSNorm) beside the rotated shared key, ``kv_lora_rank +
  qk_rope_head_dim`` wide, with no head axis.  Each layer writes its B new
  rows in place, as the K/V cache does.

The cache is stacked (L, B, S, width), its rows padded with zeros to a
multiple of the chip's 128 lanes (576 to 640 for DeepSeek-V3), as the TPU
lays a row out in memory anyway.  Unpadded, the TPU keeps such an array
with positions as the minor axis, and a step's new rows are then strided
columns whose write the compiler turns into a copy of the whole cache.

Rotary frequencies and the softmax scale follow YaRN as DeepSeek-V3's
published code applies it (``rope_factor`` 0 leaves rope unscaled).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import NEG_INF, sdpa_streaming
from .layers import apply_rope, norm_defs, rms_norm
from .params import ParamDef

CACHE_AXES = ("layers", "batch", None, None)    # (L, B, S, row)
LANES = 128


def cache_width(cfg) -> int:
    """Width of one cached row: the kv latent and the shared rotary key,
    padded to whole lanes."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // LANES) * LANES


def yarn_freqs(cfg) -> np.ndarray:
    """Rotary frequencies of the ``qk_rope_head_dim`` lanes: plain above
    the correction range, divided by ``rope_factor`` below it, and a
    linear ramp between (``find_correction_range`` of DeepSeek-V3)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if cfg.rope_factor <= 1.0:
        return freqs

    def corr_dim(rotations):
        return dim * math.log(cfg.rope_original_len
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (freqs / cfg.rope_factor * (1.0 - smooth)
            + freqs * smooth).astype(np.float32)


def softmax_scale(cfg) -> float:
    """1/sqrt(qk head width), times YaRN's mscale squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor > 1.0:
        m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
        scale *= m * m
    return scale


def mla_defs(cfg, layers: Optional[int] = None):
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    head_ax = "tp" if cfg.attn_parallel == "heads" else None
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    return {
        "wq_a": ParamDef(lead + (cfg.d_model, cfg.q_lora_rank),
                         la + ("fsdp", None)),
        "q_norm": norm_defs(cfg.q_lora_rank, "rms", layers),
        "wq_b": ParamDef(lead + (cfg.q_lora_rank, H, dn + dr),
                         la + (None, head_ax, None)),
        "wkv_a": ParamDef(lead + (cfg.d_model,
                                  cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                          la + ("fsdp", None)),
        "kv_norm": norm_defs(cfg.kv_lora_rank, "rms", layers),
        "wk_b": ParamDef(lead + (cfg.kv_lora_rank, H, dn),
                         la + (None, head_ax, None)),
        "wv_b": ParamDef(lead + (cfg.kv_lora_rank, H, cfg.v_head_dim),
                         la + (None, head_ax, None)),
        "wo": ParamDef(lead + (H, cfg.v_head_dim, cfg.d_model),
                       la + (head_ax, None, "fsdp")),
    }


def _write_rows(stack, new, layer, cache_pos):
    """Store the step's rows ``new`` (B, 1, W) at ``(layer, b,
    cache_pos[b])`` of the stacked cache (L, B, S, W): one write of B rows,
    in place when the stack is a donated buffer or a loop carry.
    Positions past the end clamp to the last row."""
    new = new.astype(stack.dtype)
    if getattr(cache_pos, "ndim", 0) == 1:
        b = jnp.arange(new.shape[0])
        return stack.at[layer, b, cache_pos].set(new[:, 0], mode="clip")
    return jax.lax.dynamic_update_slice(stack, new[None],
                                        (layer, 0, cache_pos, 0))


def mla_attention(x, p, cfg, plan, *, positions, cache=None, cache_pos=None,
                  layer=None, q_block: int = 2048, kv_block: int = 2048):
    """Latent attention block, with ``attention.attention``'s contract:

    train:    cache=None         -> (out, None)
    prefill:  cache='init'       -> (out, {"ckv": (B, cache_len, W)})
    decode:   cache={"ckv": ...} -> (out, updated cache); the cache is
              stacked (L, B, S_max, W), ``layer`` is this block's index in
              it, ``cache_pos`` the write slot (scalar or (B,)).
    """
    with jax.named_scope("mla"):
        B, S, _ = x.shape
        decode = isinstance(cache, dict)
        H, dn = cfg.n_heads, cfg.qk_nope_head_dim
        c = cfg.kv_lora_rank
        if not decode:
            x = plan.constrain(x, "batch", None, None)
        freqs = jnp.asarray(yarn_freqs(cfg))
        pos2d = positions if positions.ndim == 2 else \
            jnp.broadcast_to(positions[None, :], (B, S))

        cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]),
                      p["q_norm"]["w"])
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
        q = plan.constrain(q, "batch", None, "tp", None)
        q_nope = q[..., :dn]
        q_pe = apply_rope(q[..., dn:], pos2d, freqs=freqs)
        kv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"])
        ckv = rms_norm(kv[..., :c], p["kv_norm"]["w"])
        k_pe = apply_rope(kv[..., None, c:], pos2d, freqs=freqs)[:, :, 0]
        pad = cache_width(cfg) - c - k_pe.shape[-1]
        latent = jnp.concatenate(
            [ckv, k_pe, jnp.zeros((B, S, pad), ckv.dtype)], -1)  # (B, S, W)
        scale = softmax_scale(cfg)

        if decode:
            stack = plan.constrain(
                _write_rows(cache["ckv"], latent, layer, cache_pos),
                *CACHE_AXES)
            new_cache = {"ckv": stack}
            lat = jax.lax.dynamic_index_in_dim(stack, layer, 0,
                                               keepdims=False)
            lat = lat.astype(jnp.float32)                # (B, S_max, W)
            q_lat = jnp.einsum("bshn,chn->bshc", q_nope, p["wk_b"])
            qc = jnp.concatenate(
                [q_lat, q_pe, jnp.zeros(q_pe.shape[:-1] + (pad,),
                                        q_pe.dtype)], -1)
            qc = qc.astype(jnp.float32) * scale          # (B, 1, H, W)
            s = jnp.einsum("bshc,btc->bsht", qc, lat)
            valid = jnp.arange(lat.shape[1])[None, None, :] \
                <= positions[:, :, None]
            s = jnp.where(valid[:, :, None, :], s, NEG_INF)
            w = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bsht,btc->bshc", w, lat)[..., :c]
            out = jnp.einsum("bshc,chv->bshv", o_lat.astype(x.dtype),
                             p["wv_b"])
        else:
            k_nope = jnp.einsum("bsc,chn->bshn", ckv, p["wk_b"])
            v = jnp.einsum("bsc,chv->bshv", ckv, p["wv_b"])
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe[:, :, None],
                                          (B, S, H, k_pe.shape[-1]))], -1)
            qf = jnp.concatenate([q_nope, q_pe], -1)
            out = sdpa_streaming(qf[:, :, :, None, :], k, v, causal=True,
                                 q_block=q_block, kv_block=kv_block,
                                 scale=scale)
            new_cache = None
            if cache == "init":
                tgt = getattr(cfg, "cache_len", None) or S
                rows = jnp.pad(latent, [(0, 0), (0, max(tgt - S, 0)), (0, 0)])
                new_cache = {"ckv": plan.constrain(rows, *CACHE_AXES[1:])}

        o = jnp.einsum("bshv,hvd->bsd", out, p["wo"],
                       preferred_element_type=jnp.bfloat16)
        if not decode:
            o = plan.constrain(o, "batch", "sp", None)
        return o, new_cache
