"""Plain reference of a dense decoder with grouped-query attention (the
Llama/Yi block), in float32 ``jax.numpy``, layer by layer.

It follows the published description (Yi-34B is Llama's architecture):
pre-norm RMSNorm, rotary positions, causal attention with ``n_kv`` key and
value heads shared by groups of query heads, a SwiGLU MLP, a final RMSNorm
and an untied output head.  Departures, each of them a convention of the
program that the reference takes on so that one set of weights serves
both:

- an RMSNorm's scale is stored as ``w`` and applied as ``1 + w``;
- rotary pairs are interleaved (lanes 2i, 2i+1), where Hugging Face's
  layout pairs lane i with lane i + hd/2: the two are the same up to a
  fixed permutation of the query and key projection columns.

It imports nothing of the program.  Its weights are made again from the
seed by :mod:`weights`, one layer at a time, under the names of
:func:`layout`, so it runs after the program's state is freed and fits on
the chip beside nothing else.

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
Q_BLOCK = 256       # query rows per attention block
BUCKET = 512        # sequences are padded to a multiple of this
ROWS = 128          # logit rows are padded to a multiple of this


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h, "f": c["intermediate_size"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"])}


def layout(c: dict) -> dict:
    """The program's parameter tree, leaf by leaf: ``{name: (shape without
    the layer axis, layers or 0, std)}``."""
    m = dims(c)
    d, h, kv, hd, f, L = m["d"], m["h"], m["kv"], m["hd"], m["f"], \
        m["layers"]
    s = "stacks/dense/"
    return {
        "embed/emb": ((m["v"], d), 0, 1.0),
        "embed/unemb": ((d, m["v"]), 0, 1 / math.sqrt(d)),
        "final_norm/w": ((d,), 0, NORM_STD),
        s + "ln1/w": ((d,), L, NORM_STD),
        s + "ln2/w": ((d,), L, NORM_STD),
        s + "attn/wq": ((d, h, hd), L, 1 / math.sqrt(d)),
        s + "attn/wk": ((d, kv, hd), L, 1 / math.sqrt(d)),
        s + "attn/wv": ((d, kv, hd), L, 1 / math.sqrt(d)),
        s + "attn/wo": ((h, hd, d), L, 1 / math.sqrt(h * hd)),
        s + "mlp/wi": ((d, f), L, 1 / math.sqrt(d)),
        s + "mlp/wg": ((d, f), L, 1 / math.sqrt(d)),
        s + "mlp/wo": ((f, d), L, 1 / math.sqrt(f)),
    }


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


def _rope(x, pos, theta):
    """x: (S, H, hd); interleaved pairs."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _block(x, p, m, precision):
    """One decoder layer over one sequence x: (S, d), float32."""
    S = x.shape[0]
    pos = jnp.arange(S)
    xn = _rms(x, p["ln1"], m["eps"])
    q = _rope(_mm("sd,dhk->shk", xn, p["wq"], precision), pos, m["theta"])
    k = _rope(_mm("sd,dhk->shk", xn, p["wk"], precision), pos, m["theta"])
    v = _mm("sd,dhk->shk", xn, p["wv"], precision)
    g = m["h"] // m["kv"]
    q = q.reshape(S, m["kv"], g, m["hd"]) / math.sqrt(m["hd"])
    outs = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)
        s = _mm("qhgk,shk->hgqs", q[lo:hi], k, precision)
        mask = jnp.arange(S)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("hgqs,shk->qhgk", w, v, precision))
    a = jnp.concatenate(outs, 0).reshape(S, m["h"], m["hd"])
    x = x + _mm("shk,hkd->sd", a, p["wo"], precision)
    xn = _rms(x, p["ln2"], m["eps"])
    hmid = jax.nn.silu(_mm("sd,df->sf", xn, p["wg"], precision)) \
        * _mm("sd,df->sf", xn, p["wi"], precision)
    return x + _mm("sf,fd->sd", hmid, p["wo2"], precision)


_LAYER_NAMES = {"ln1": "ln1/w", "ln2": "ln2/w", "wq": "attn/wq",
                "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
                "wi": "mlp/wi", "wg": "mlp/wg", "wo2": "mlp/wo"}


def _layer_params(key, lay, layer, dtype):
    return {k: W.layer_leaf(key, "stacks/dense/" + n, layer,
                            lay["stacks/dense/" + n][0],
                            lay["stacks/dense/" + n][2],
                            dtype).astype(jnp.float32)
            for k, n in _LAYER_NAMES.items()}


def logits_at(seed: int, c: dict, seqs, positions, dtype=jnp.bfloat16,
              precision: str = "f32"):
    """Logits of the cut model over each token sequence in ``seqs``, at
    the given positions of each: a list of (len(positions[i]), vocab)
    float32 numpy arrays.  Weights are made from ``seed`` in ``dtype``,
    the type the program holds them in, and computed on in float32
    (``precision="f32"``, matrix products at ``highest``) or float8."""
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
        block = jax.jit(lambda x, p: _block(x, p, m, precision))
        make = jax.jit(lambda l: _layer_params(key, lay, l, dtype))
        for layer in range(m["layers"]):
            p = make(jnp.uint32(layer))
            xs = [block(x, p) for x in xs]
            del p
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
        return out
