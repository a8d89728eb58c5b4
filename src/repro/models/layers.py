"""Common layers: norms, GLU MLPs, embeddings, RoPE / M-RoPE.

All matmuls run in bf16 with fp32 normalization/softmax statistics.
Sharding is expressed only through logical axes (models/params.py) and
``plan.constrain`` — never mesh axes directly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .params import ParamDef


# -- norms -------------------------------------------------------------------
def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    x = (x - m) * jax.lax.rsqrt(v + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_defs(d_model: int, kind: str = "rms", layers: Optional[int] = None):
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()
    if kind == "rms":
        return {"w": ParamDef(lead + (d_model,), lax_ + (None,), init="zeros")}
    return {"w": ParamDef(lead + (d_model,), lax_ + (None,), init="ones"),
            "b": ParamDef(lead + (d_model,), lax_ + (None,), init="zeros")}


def apply_norm(x, p, kind: str = "rms"):
    if kind == "rms":
        return rms_norm(x, p["w"])
    return layer_norm(x, p["w"], p["b"])


# -- GLU MLP (SwiGLU / GeGLU) --------------------------------------------------
def mlp_defs(d_model: int, d_ff: int, layers: Optional[int] = None):
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    return {
        "wi": ParamDef(lead + (d_model, d_ff), la + ("fsdp", "tp")),
        "wg": ParamDef(lead + (d_model, d_ff), la + ("fsdp", "tp")),
        "wo": ParamDef(lead + (d_ff, d_model), la + ("tp", "fsdp")),
    }


def mlp(x, p, act: str = "silu", plan=None):
    if plan is not None:
        # SP boundary: gather the (bf16) norm output over the seq shards
        # here, not at some f32 intermediate GSPMD picks
        x = plan.constrain(x, "batch", None, None)
    wi = plan.gather_fsdp(p["wi"], ("fsdp", "tp")) if plan else p["wi"]
    wg = plan.gather_fsdp(p["wg"], ("fsdp", "tp")) if plan else p["wg"]
    wo = plan.gather_fsdp(p["wo"], ("tp", "fsdp")) if plan else p["wo"]
    a = jnp.einsum("bsd,df->bsf", x, wi)
    g = jnp.einsum("bsd,df->bsf", x, wg)
    g = jax.nn.gelu(g) if act == "gelu" else jax.nn.silu(g)
    # bf16 partials + immediate sp constraint: the cross-shard reduction
    # lowers to a bf16 reduce-scatter instead of an f32 all-reduce
    o = jnp.einsum("bsf,fd->bsd", a * g, wo,
                   preferred_element_type=jnp.bfloat16)
    if plan is not None:
        o = plan.constrain(o, "batch", "sp", None)
    return o


# -- embeddings ----------------------------------------------------------------
def embed_defs(vocab: int, d_model: int, tie: bool = False):
    d = {"emb": ParamDef((vocab, d_model), ("tp", "fsdp"), init="embed",
                         scale=1.0)}
    if not tie:
        d["unemb"] = ParamDef((d_model, vocab), ("fsdp", "tp"))
    return d


def embed(tokens, p, d_model: int):
    # gather; vocab-sharded -> XLA turns this into a sharded one-hot matmul
    return p["emb"][tokens].astype(jnp.bfloat16)


def unembed(x, p):
    w = p.get("unemb")
    if w is None:
        w = p["emb"].T
    return jnp.einsum("bsd,dv->bsv", x, w)


# -- rotary position embeddings -------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 1e4, freqs=None):
    """x: (B, S, H, D); positions: (B, S) int32; ``freqs`` (D/2,) in place
    of the plain ones of ``theta`` (a scaled rope)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)                   # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B,S,d/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_mrope(x, positions_thw, theta: float = 1e4, sections=(16, 24, 24)):
    """Qwen2-VL M-RoPE: head_dim/2 split into (t, h, w) frequency sections,
    each rotated by its own position id.  positions_thw: (3, B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = rope_freqs(d, theta)                       # (half,)
    # build per-frequency position: section s of the spectrum uses pos[s]
    sec = jnp.zeros((half,), jnp.int32)
    start = 0
    tot = sum(sections)
    scaled = [int(round(s / tot * half)) for s in sections]
    scaled[-1] = half - sum(scaled[:-1])
    for i, n in enumerate(scaled):
        sec = sec.at[start:start + n].set(i)
        start += n
    # (B,S,half): select the right (t/h/w) position stream per frequency
    p = jnp.moveaxis(positions_thw, 0, -1).astype(jnp.float32)   # (B,S,3)
    psel = p[..., sec]                                           # (B,S,half)
    ang = psel * freqs[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)
