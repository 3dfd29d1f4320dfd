"""deepseek-v3.2-671b-a37b — DeepSeek-V3.2: DeepSeek sparse attention (a
lightning indexer selecting 2048 positions a query for latent attention),
3 dense layers, then 1 shared + 256 routed experts (top-8)
[https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json].

The multi-token-prediction module (``num_nextn_predict_layers`` 1) is not
mapped: the model is served without speculative decoding. Routing is
priced as uniform top-8 (no ``n_group``/``topk_group`` term) and the rope
has no YaRN term, as for Kimi-K2."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v3.2-671b-a37b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=18432,                    # dense layers 0-2 (intermediate_size)
    vocab_size=129280,
    num_experts=256,
    experts_per_token=8,
    first_k_dense=3,               # first_k_dense_replace
    moe_d_ff=2048,                 # moe_intermediate_size
    n_shared_experts=1,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    index_n_heads=64,
    index_head_dim=128,
    index_topk=2048,
    act="swiglu",
    norm="rms",
    rope_theta=10000.0,
)
