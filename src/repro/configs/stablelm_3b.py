"""stablelm-3b — StableLM-3B-4E1T: a dense decoder with LayerNorm, 32 layers
[https://huggingface.co/stabilityai/stablelm-3b-4e1t/blob/main/config.json]."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="stablelm-3b",
    family="dense",
    num_layers=32,
    d_model=2560,
    num_heads=32,
    num_kv_heads=32,
    d_ff=6912,
    vocab_size=50304,
    act="swiglu",
    norm="ln",
)
