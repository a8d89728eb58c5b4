"""Operations and bytes of one decode step of a DeepSeek-V3-style
configuration (latent attention, one chip's share of the experts), from
its shapes and the engine's expert counters alone, for the roofline and
``mfu`` metrics of its cells.

Counts are of the work the algorithm needs, not of what a program happens
to do: every attention and dense weight once, the latent cache at each
request's live context (absorbed attention: scores over ``c_kv`` and the
rotary key, values over ``c_kv``), and of each expert layer the router,
the shared expert and those held experts that took a token.  Multiply-adds
count as two operations.  Weights and cache are bfloat16.
"""

from __future__ import annotations

BF16 = 2


def dims(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    q, kv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    fe = c["moe_intermediate_size"]
    L, Ld = c["num_hidden_layers"], c["first_k_dense_replace"]
    return {"d": d, "H": H, "kv": kv, "dr": dr, "L": L, "Ld": Ld,
            "Lm": L - Ld, "v": c["vocab_size"],
            "mla": d * q + q * H * (dn + dr) + d * (kv + dr) + kv * H * dn
            + kv * H * dv + H * dv * d,
            "mlp": 3 * d * c["intermediate_size"],
            "router": d * c["published"]["n_routed_experts"],
            "shared": 3 * d * fe * c["n_shared_experts"],
            "expert": 3 * d * fe}


def latent_bytes_per_token(c: dict) -> int:
    """Cache bytes of one position over every layer."""
    m = dims(c)
    return m["L"] * (m["kv"] + m["dr"]) * BF16


def mla_decode(c: dict, batch: float, kv_positions: float):
    """(flops, bytes) of the attention of every layer in one decode step
    that advances ``batch`` requests whose caches hold ``kv_positions``
    positions between them."""
    m = dims(c)
    flops = 2.0 * batch * m["L"] * m["mla"] \
        + 2.0 * m["L"] * m["H"] * (2 * m["kv"] + m["dr"]) * kv_positions
    byts = m["L"] * m["mla"] * BF16 \
        + (kv_positions + batch) * latent_bytes_per_token(c)
    return flops, byts


def moe_decode(c: dict, batch: float, local_assignments: float,
               experts_touched: float):
    """(flops, bytes) of every expert layer in one decode step: the router
    and the shared expert for ``batch`` tokens, and the held experts for
    the ``local_assignments`` token slots routed to them, reading the
    weights of the ``experts_touched`` (summed over layers) that took any."""
    m = dims(c)
    flops = 2.0 * batch * m["Lm"] * (m["router"] + m["shared"]) \
        + 2.0 * local_assignments * m["expert"]
    byts = (m["Lm"] * (m["router"] + m["shared"])
            + experts_touched * m["expert"]) * BF16
    return flops, byts


def decode_step(c: dict, batch: float, kv_positions: float,
                local_assignments: float, experts_touched: float):
    """(flops, bytes) of a whole decode step: attention, the dense MLPs,
    the expert layers, the output head, and the embedding rows gathered."""
    m = dims(c)
    fa, ba = mla_decode(c, batch, kv_positions)
    fm, bm = moe_decode(c, batch, local_assignments, experts_touched)
    rest = m["Ld"] * m["mlp"] + m["d"] * m["v"]
    return (fa + fm + 2.0 * batch * rest,
            ba + bm + rest * BF16 + batch * m["d"] * BF16)
