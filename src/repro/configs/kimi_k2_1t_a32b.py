"""kimi-k2-1t-a32b — Kimi-K2-Instruct: latent attention, 1 shared + 384
routed experts (top-8), dense layer 0
[https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json].

``num_kv_heads`` is the published ``num_key_value_heads``; latent attention
does not read it (the cache is one ``kv_lora_rank + qk_rope_head_dim``
vector per token, shared by all heads)."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=64,
    num_kv_heads=64,
    d_ff=18432,                    # dense layer 0 (intermediate_size)
    vocab_size=163840,
    num_experts=384,
    experts_per_token=8,
    first_layer_dense=True,
    moe_d_ff=2048,                 # moe_intermediate_size
    n_shared_experts=1,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    act="swiglu",
    norm="rms",
    rope_theta=50000.0,
)
