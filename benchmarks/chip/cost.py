"""Operations and bytes of one call, from shapes alone, for each program
that a roofline or ``mfu`` metric reads.

Counts are of the work the algorithm needs, not of what a program happens
to do: a decode step reads every weight once and the key/value cache at
each request's live context, however much cache the program reserves.
Multiply-adds count as two operations.  Weights and cache are bfloat16.
"""

from __future__ import annotations

BF16 = 2


def _dense(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    kv = c["num_key_value_heads"]
    f = c["intermediate_size"]
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return {"d": d, "h": h, "hd": hd, "kv": kv, "v": c["vocab_size"],
            "L": c["num_hidden_layers"], "layer_params": layer,
            "kv_bytes_per_token": 2 * kv * hd * BF16 * c["num_hidden_layers"]}


def dense_weight_bytes(c: dict) -> int:
    """Weights a decode step streams: every layer and the output head (the
    embedding table is gathered row by row)."""
    m = _dense(c)
    return (m["L"] * m["layer_params"] + m["d"] * m["v"]) * BF16


def kv_bytes_per_token(c: dict) -> int:
    return _dense(c)["kv_bytes_per_token"]


def dense_decode_step(c: dict, batch: float, kv_positions: float):
    """(flops, bytes) of one decode step that advances ``batch`` requests
    whose caches hold ``kv_positions`` positions between them."""
    m = _dense(c)
    flops = 2.0 * batch * (m["L"] * m["layer_params"] + m["d"] * m["v"]) \
        + 4.0 * m["L"] * m["h"] * m["hd"] * kv_positions
    byts = (dense_weight_bytes(c)
            + kv_positions * m["kv_bytes_per_token"]        # cache read
            + batch * m["kv_bytes_per_token"]               # cache write
            + batch * m["d"] * BF16)                        # embedding rows
    return flops, byts


def roofline_s(flops: float, byts: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_bf16"], byts / peak["hbm_bytes_per_s"])
