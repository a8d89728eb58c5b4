"""Architecture config schema.

Every assigned architecture is a ``Config`` in its own module
(``configs/<id>.py``) selectable via ``--arch <id>`` (configs/registry.py).
``cache_specs`` builds ShapeDtypeStruct stand-ins (shardable, no
allocation) for a model's decode caches.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass
class Config:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    source: str = ""                 # provenance note

    # attention
    attn_kind: str = "full"          # full | swa | mla
    window: int = 0
    rope_theta: float = 1e4
    # YaRN (DeepSeek-V3's rope scaling): factor 0 leaves rope unscaled
    rope_factor: float = 0.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # latent attention (attn_kind="mla"): low-rank q and kv, a decoupled
    # rotary part shared by the heads; the cache holds the kv latent
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    use_rope: bool = True
    mrope: bool = False
    attn_parallel: str = "heads"     # heads | cp
    padded_heads: int = 0            # TP head padding (deployment option)
    n_kv_eff: int = 0                # kv heads after TP replication
    cache_len: Optional[int] = None  # set by prefill()/cache_defs()

    # norms / activations
    norm: str = "rms"                # rms | ln
    act: str = "silu"                # silu | gelu

    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    moe_mode: str = "ep"             # ep | tp
    first_k_dense: int = 0           # leading dense layers before the MoE
    router_score: str = "softmax"    # softmax | sigmoid
    router_bias: bool = False        # noaux_tc: a bias picks, never weighs
    routed_scale: float = 1.0        # routed_scaling_factor
    # this chip's share of the experts: it routes over all n_experts and
    # computes experts [expert_offset, expert_offset + n_experts_held) for
    # every token routed to them (0: the layer holds them all)
    n_experts_held: int = 0
    expert_offset: int = 0

    # ssm / linear recurrence
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_groups: int = 1
    ssm_conv: int = 4
    gla_chunk: int = 256

    # hybrid (zamba) / encdec (whisper)
    shared_attn_window: int = 0
    segments_spec: Optional[List[Tuple[str, int]]] = None
    enc_layers: int = 0
    dec_layers: int = 0
    enc_len: int = 4096              # cross-attention context at decode

    # training
    tie_embeddings: bool = False
    optimizer: str = "adamw"         # adamw | adafactor
    loss_chunks: int = 1
    n_microbatches: int = 1
    q_block: int = 2048
    kv_block: int = 2048

    def __post_init__(self):
        if self.head_dim == 0:
            self.head_dim = self.d_model // self.n_heads
        if self.n_kv_eff == 0:
            self.n_kv_eff = (max(self.n_kv_heads, 16)
                             if self.attn_parallel == "heads"
                             else self.n_kv_heads)

    # -- structure -----------------------------------------------------------
    @property
    def segments(self) -> List[Tuple[str, int]]:
        if self.segments_spec is not None:
            return self.segments_spec
        if self.family == "encdec":
            return [("enc", self.enc_layers), ("dec", self.dec_layers)]
        if self.family == "moe":
            if self.first_k_dense:
                return [("dense", self.first_k_dense),
                        ("moe", self.n_layers - self.first_k_dense)]
            return [("moe", self.n_layers)]
        return [("dense", self.n_layers)]

    def stack_sizes(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for kind, count in self.segments:
            out[kind] = out.get(kind, 0) + (1 if kind == "shared_attn" else count)
        return out

    # -- parameter count --------------------------------------------------------
    def n_params(self) -> int:
        from ..models.lm import LM
        from ..models.params import count_params
        return count_params(LM(self).param_defs())

    # -- reduced config for CPU smoke tests ------------------------------------
    def reduced(self) -> "Config":
        r = dataclasses.replace(
            self,
            n_layers=2, d_model=64,
            n_heads=min(self.n_heads, 4),
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=16, d_ff=128, vocab=256,
            n_kv_eff=min(self.n_kv_heads, 2),
            window=min(self.window, 32) if self.window else 0,
            shared_attn_window=min(self.shared_attn_window, 32)
            if self.shared_attn_window else 0,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_d_ff=64 if self.moe_d_ff else 0,
            n_experts_held=min(self.n_experts_held, 2)
            if self.n_experts_held else 0,
            expert_offset=0,
            q_lora_rank=32 if self.q_lora_rank else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_head_dim=16 if self.qk_nope_head_dim else 0,
            qk_rope_head_dim=8 if self.qk_rope_head_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_headdim=16 if self.ssm_state or self.family == "ssm" else 64,
            gla_chunk=16,
            enc_layers=min(self.enc_layers, 2),
            dec_layers=min(self.dec_layers, 2),
            enc_len=64,
            loss_chunks=1, q_block=32, kv_block=32,
            segments_spec=self._reduced_segments(),
        )
        return r

    def _reduced_segments(self):
        if self.segments_spec is None:
            return None
        if self.family == "hybrid":
            return [("mamba2", 2), ("shared_attn", 1), ("mamba2", 2)]
        if self.family == "ssm":
            return [("mlstm", 2), ("slstm", 1)]
        return None


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins)
# ---------------------------------------------------------------------------
def _sds(shape, dtype, plan=None, axes=None):
    if plan is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=plan.sharding_for(axes, shape))


def cache_specs(cfg: Config, B: int, S: int, plan=None):
    from ..models.lm import LM
    defs = LM(cfg).cache_defs(B, S)
    def leaf(t):
        shape, dtype, axes = t
        return _sds(shape, dtype, plan, axes)
    return jax.tree.map(leaf, defs,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and len(x) == 3 and isinstance(x[0], tuple))
