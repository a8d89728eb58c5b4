"""Config for --arch kimi-k2-1t-a32b: Kimi-K2-Instruct as published
(DeepSeek-V3's architecture at 384 experts): latent attention, one leading
dense layer, then sigmoid-scored top-8 routing with a correction bias
(noaux_tc), one shared expert, YaRN rope."""

from .base import Config
from .registry import register

CONFIG = register(Config(
    name="kimi-k2-1t-a32b", family="moe",
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=64,
    d_ff=18432, vocab=163840, act="silu", attn_parallel="heads",
    attn_kind="mla", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=50000.0, rope_factor=32.0, rope_original_len=4096,
    rope_beta_fast=1.0, rope_beta_slow=1.0, rope_mscale_all_dim=1.0,
    n_experts=384, top_k=8, moe_d_ff=2048, n_shared_experts=1,
    first_k_dense=1, router_score="sigmoid", router_bias=True,
    routed_scale=2.827, moe_mode="ep", optimizer="adafactor",
    loss_chunks=4, q_block=512, kv_block=512))
