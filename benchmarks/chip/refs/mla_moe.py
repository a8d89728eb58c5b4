"""Plain reference of DeepSeek-V3's decoder as Kimi-K2 publishes it:
multi-head latent attention and a sigmoid-routed mixture of experts, in
float32 ``jax.numpy``, layer by layer, for one chip's share of the model.

It follows the published description (DeepSeek-V3, arXiv:2412.19437, and
its released inference code; Kimi-K2's config.json):

- pre-norm RMSNorm blocks; the first ``first_k_dense_replace`` layers have a
  dense SwiGLU MLP, the rest a mixture of experts;
- attention: ``q = W_UQ RMSNorm(W_DQ x)``, ``[c_kv, k_rope] = W_DKV x``,
  ``k_nope = W_UK RMSNorm(c_kv)``, ``v = W_UV RMSNorm(c_kv)``; the rotary
  key is one vector shared by all heads; causal softmax over ``q_nope .
  k_nope + q_rope . k_rope`` scaled by ``(qk_nope + qk_rope)^-1/2`` times
  YaRN's ``mscale^2``; YaRN rotary frequencies (``rope_scaling``);
- experts: sigmoid scores of the ``n_routed_experts`` published; the
  correction bias (``noaux_tc``) is added to pick the top
  ``num_experts_per_tok`` and not to weigh them; the picked scores are
  normalized and scaled by ``routed_scaling_factor``; one shared expert
  is added to every token.

Departures, each a convention of the cut or of the program that the
reference takes on so that one set of weights serves both:

- the chip's share: of the published experts only ``n_routed_experts``
  (the file's count) from ``expert_offset`` on are computed; the router
  still scores all of them (``published.n_routed_experts``), and what the
  absent experts would add is left out, as the chips that hold them would
  add it; the vocabulary is the file's ``vocab_size`` rows (a slice);
- an RMSNorm's scale is stored as ``w`` and applied as ``1 + w``;
- rotary pairs are interleaved (lanes 2i, 2i+1), as DeepSeek-V3's released
  code rotates them;
- YaRN's frequencies are applied at every length (as Hugging Face's
  DeepSeek-V3 does; the released code applies them past 4,096 positions);
  its cos/sin scale ``mscale / mscale_all_dim`` is 1 here.

It computes attention decompressed, per head, never from a cache.  It
imports nothing of the program.  Its weights are made again from the seed
by :mod:`weights`, one layer at a time, under the names of
:func:`layout`, so it runs after the program's state is freed.

``precision="fp8"`` is the control: every matrix product in float8 (e4m3)
with a scale per tensor, the step below the bfloat16 the program serves.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

NORM_STD = 0.05     # spread of the stored norm scales (applied as 1 + w)
BIAS_STD = 0.05     # spread of the routing correction bias
Q_BLOCK = 256       # query rows per attention block
BUCKET = 512        # sequences are padded to a multiple of this
ROWS = 128          # logit rows are padded to a multiple of this


def dims(c: dict) -> dict:
    rs = c.get("rope_scaling") or {}
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
            "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "f": c["intermediate_size"],
            "fe": c["moe_intermediate_size"],
            "experts": c["published"]["n_routed_experts"],
            "held": c["n_routed_experts"], "offset": c["expert_offset"],
            "k": c["num_experts_per_tok"],
            "scale": float(c["routed_scaling_factor"]),
            "shared": c["n_shared_experts"],
            "dense": c["first_k_dense_replace"],
            "layers": c["num_hidden_layers"], "v": c["vocab_size"],
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
            "factor": float(rs.get("factor", 0.0)),
            "orig": int(rs.get("original_max_position_embeddings", 4096)),
            "beta_fast": float(rs.get("beta_fast", 32)),
            "beta_slow": float(rs.get("beta_slow", 1)),
            "mscale_all_dim": float(rs.get("mscale_all_dim", 0.0))}


def _attn_layout(m, s, L):
    d, h, dn, dr = m["d"], m["h"], m["dn"], m["dr"]
    return {
        s + "attn/wq_a": ((d, m["q_rank"]), L, 1 / math.sqrt(d)),
        s + "attn/q_norm/w": ((m["q_rank"],), L, NORM_STD),
        s + "attn/wq_b": ((m["q_rank"], h, dn + dr), L,
                          1 / math.sqrt(m["q_rank"])),
        s + "attn/wkv_a": ((d, m["kv_rank"] + dr), L, 1 / math.sqrt(d)),
        s + "attn/kv_norm/w": ((m["kv_rank"],), L, NORM_STD),
        s + "attn/wk_b": ((m["kv_rank"], h, dn), L,
                          1 / math.sqrt(m["kv_rank"])),
        s + "attn/wv_b": ((m["kv_rank"], h, m["dv"]), L,
                          1 / math.sqrt(m["kv_rank"])),
        s + "attn/wo": ((h, m["dv"], d), L, 1 / math.sqrt(h * m["dv"])),
        s + "ln1/w": ((d,), L, NORM_STD),
        s + "ln2/w": ((d,), L, NORM_STD),
    }


def _glu_layout(prefix, d, f, L, lead=()):
    return {prefix + "wi": (lead + (d, f), L, 1 / math.sqrt(d)),
            prefix + "wg": (lead + (d, f), L, 1 / math.sqrt(d)),
            prefix + "wo": (lead + (f, d), L, 1 / math.sqrt(f))}


def layout(c: dict) -> dict:
    """The program's parameter tree, leaf by leaf: ``{name: (shape without
    the layer axis, layers or 0, std)}``."""
    m = dims(c)
    d, Ld, Lm = m["d"], m["dense"], m["layers"] - m["dense"]
    out = {
        "embed/emb": ((m["v"], d), 0, 1.0),
        "embed/unemb": ((d, m["v"]), 0, 1 / math.sqrt(d)),
        "final_norm/w": ((d,), 0, NORM_STD),
    }
    out.update(_attn_layout(m, "stacks/dense/", Ld))
    out.update(_glu_layout("stacks/dense/mlp/", d, m["f"], Ld))
    s = "stacks/moe/"
    out.update(_attn_layout(m, s, Lm))
    out.update({s + "moe/router": ((d, m["experts"]), Lm, 1 / math.sqrt(d)),
                s + "moe/router_bias": ((m["experts"],), Lm, BIAS_STD)})
    out.update(_glu_layout(s + "moe/", d, m["fe"], Lm, (m["held"],)))
    out.update(_glu_layout(s + "moe/shared/", d, m["fe"] * m["shared"], Lm))
    return out


# ---------------------------------------------------------------------------
def _q8(x):
    """x as float8 e4m3 with one scale for the tensor: (values, scale)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn), s


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a8, sa = _q8(a)
        b8, sb = _q8(b)
        return jnp.einsum(spec, a8, b8,
                          preferred_element_type=jnp.float32) * (sa * sb)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _yarn(m):
    """(rotary frequencies of the dr/2 pairs, softmax scale)."""
    dim, base = m["dr"], m["theta"]
    freqs = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (m["dn"] + m["dr"]) ** -0.5
    if m["factor"] > 1.0:
        def corr(rot):
            return dim * math.log(m["orig"] / (rot * 2 * math.pi)) \
                / (2 * math.log(base))
        lo = max(math.floor(corr(m["beta_fast"])), 0)
        hi = min(math.ceil(corr(m["beta_slow"])), dim - 1)
        hi = hi + 0.001 if hi == lo else hi
        ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
        freqs = freqs / m["factor"] * ramp + freqs * (1.0 - ramp)
        mscale = 0.1 * m["mscale_all_dim"] * math.log(m["factor"]) + 1.0
        scale *= mscale * mscale
    return freqs.astype(np.float32), scale


def _rope(x, pos, freqs):
    """x: (S, H, dr); interleaved pairs."""
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(x, p, m, precision):
    """Latent attention over one sequence x: (S, d), decompressed."""
    S = x.shape[0]
    pos = jnp.arange(S)
    freqs, scale = _yarn(m)
    dn, kr = m["dn"], m["kv_rank"]
    cq = _rms(_mm("sd,dr->sr", x, p["wq_a"], precision), p["q_norm"],
              m["eps"])
    q = _mm("sr,rhk->shk", cq, p["wq_b"], precision)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, freqs)], -1)
    kv = _mm("sd,dc->sc", x, p["wkv_a"], precision)
    ckv = _rms(kv[:, :kr], p["kv_norm"], m["eps"])
    k_rope = _rope(kv[:, None, kr:], pos, freqs)              # (S, 1, dr)
    k_nope = _mm("sc,chn->shn", ckv, p["wk_b"], precision)
    v = _mm("sc,chv->shv", ckv, p["wv_b"], precision)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (S, m["h"], m["dr"]))], -1)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)
        s = _mm("qhk,shk->hqs", q[lo:hi], k, precision) * scale
        mask = jnp.arange(S)[None, :] <= jnp.arange(lo, hi)[:, None]
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(_mm("hqs,shv->qhv", w, v, precision))
    return _mm("shv,hvd->sd", jnp.concatenate(outs, 0), p["wo_a"], precision)


def _glu(x, wi, wg, wo, precision):
    h = jax.nn.silu(_mm("sd,df->sf", x, wg, precision)) \
        * _mm("sd,df->sf", x, wi, precision)
    return _mm("sf,fd->sd", h, wo, precision)


def _experts(x, p, m, precision):
    """The held experts' part and the shared expert, for each token, and
    the routing of the held experts: (out, picked (S, held) bool, the
    least change of a held expert's biased score that would move it
    across the k-th largest (S,))."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", x, p["router"], precision))
    pick = scores + p["router_bias"]
    top, idx = jax.lax.top_k(pick, m["k"] + 1)
    idx = idx[:, :m["k"]]
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * m["scale"]
    out = _glu(x, p["s_wi"], p["s_wg"], p["s_wo"], precision)
    for e in range(m["held"]):
        gate = jnp.sum(jnp.where(idx == m["offset"] + e, w, 0.0), -1)
        out = out + gate[:, None] * _glu(x, p["wi"][e], p["wg"][e],
                                         p["wo"][e], precision)
    held = pick[:, m["offset"]:m["offset"] + m["held"]]
    picked = held >= top[:, m["k"] - 1:m["k"]]
    # a picked expert leaves below the (k+1)-th score, another enters
    # above the k-th
    tie = jnp.where(picked, held - top[:, m["k"]:], top[:, m["k"] - 1:m["k"]]
                    - held).min(-1)
    return out, picked, tie


def _block(x, p, m, precision, moe):
    """(the block's output, and for an expert layer its routing as
    :func:`_experts` gives it)."""
    x = x + _attention(_rms(x, p["ln1"], m["eps"]), p, m, precision)
    xn = _rms(x, p["ln2"], m["eps"])
    if moe:
        out, picked, tie = _experts(xn, p, m, precision)
        return x + out, (picked, tie)
    return x + _glu(xn, p["wi"], p["wg"], p["wo"], precision), None


_ATTN = {"ln1": "ln1/w", "ln2": "ln2/w", "wq_a": "attn/wq_a",
         "q_norm": "attn/q_norm/w", "wq_b": "attn/wq_b",
         "wkv_a": "attn/wkv_a", "kv_norm": "attn/kv_norm/w",
         "wk_b": "attn/wk_b", "wv_b": "attn/wv_b", "wo_a": "attn/wo"}
_STACKS = {   # moe? -> (stack, {reference name: program leaf})
    False: ("stacks/dense/", dict(_ATTN, wi="mlp/wi", wg="mlp/wg",
                                  wo="mlp/wo")),
    True: ("stacks/moe/", dict(_ATTN, router="moe/router",
                               router_bias="moe/router_bias", wi="moe/wi",
                               wg="moe/wg", wo="moe/wo",
                               s_wi="moe/shared/wi", s_wg="moe/shared/wg",
                               s_wo="moe/shared/wo")),
}


def _layer_params(key, lay, moe, layer, dtype):
    stack, names = _STACKS[moe]
    return {k: W.layer_leaf(key, stack + n, layer, lay[stack + n][0],
                            lay[stack + n][2], dtype).astype(jnp.float32)
            for k, n in names.items()}


def logits_at(seed: int, c: dict, seqs, positions, dtype=jnp.bfloat16,
              precision: str = "f32", routes: bool = False):
    """Logits of the cut model over each token sequence in ``seqs``, at
    the given positions of each: a list of (len(positions[i]), vocab)
    float32 numpy arrays.  Weights are made from ``seed`` in ``dtype``,
    the type the program holds them in, and computed on in float32
    (``precision="f32"``, matrix products at ``highest``) or float8.

    With ``routes``, also each sequence's routing at every position of it:
    a list of (picked (expert layers, len, held) bool, tie (expert layers,
    len) float32) as :func:`_experts` gives them."""
    m = dims(c)
    lay = layout(c)
    key = W.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        emb_spec = lay["embed/emb"]
        emb = W.flat_leaf(key, "embed/emb", emb_spec[0], emb_spec[2], dtype)
        xs = []
        for t in seqs:
            padded = np.zeros(-(-len(t) // BUCKET) * BUCKET, np.int32)
            padded[:len(t)] = t
            xs.append(emb[jnp.asarray(padded)].astype(jnp.float32))
        del emb

        block = {moe: jax.jit(lambda x, p, _moe=moe: _block(
            x, p, m, precision, _moe)) for moe in (False, True)}
        make = {moe: jax.jit(lambda l, _moe=moe: _layer_params(
            key, lay, _moe, l, dtype)) for moe in (False, True)}
        route = [[] for _ in xs]
        for layer in range(m["layers"]):
            moe = layer >= m["dense"]
            p = make[moe](jnp.uint32(layer - m["dense"] if moe else layer))
            outs = [block[moe](x, p) for x in xs]
            xs = [x for x, _ in outs]
            if moe and routes:
                for r, (_, info) in zip(route, outs):
                    r.append(jax.device_get(info))
            del p, outs
        fn, un = lay["final_norm/w"], lay["embed/unemb"]
        wn = W.flat_leaf(key, "final_norm/w", fn[0], fn[2],
                         dtype).astype(jnp.float32)
        wu = W.flat_leaf(key, "embed/unemb", un[0], un[2],
                         dtype).astype(jnp.float32)
        head = jax.jit(lambda h, wn, wu: _mm(
            "sd,dv->sv", _rms(h, wn, m["eps"]), wu, precision))
        out = []
        for x, pos in zip(xs, positions):
            n = len(pos)
            rows = np.full(-(-n // ROWS) * ROWS, pos[-1], np.int32)
            rows[:n] = pos
            out.append(np.asarray(head(x[jnp.asarray(rows)], wn, wu))[:n])
        if not routes:
            return out
        return out, [(np.stack([p[:len(t)] for p, _ in r]),
                      np.stack([q[:len(t)] for _, q in r]))
                     for r, t in zip(route, seqs)]
