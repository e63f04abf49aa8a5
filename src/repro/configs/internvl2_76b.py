"""InternVL2-76B — InternViT frontend (stub) + InternLM2/Llama3-70B-class
backbone [arXiv:2404.16821].

80L d_model=8192 64H (GQA kv=8) d_ff=28672 vocab=128256. The vision tower is
a stub: input_specs() provides 256 precomputed patch embeddings per image,
already projected to d_model; they are prepended to the text tokens."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="internvl2-76b",
    family="vlm",
    n_layers=80,
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    d_ff=28672,
    vocab=128256,
    rope_theta=5e5,
    source="arXiv:2404.16821; unverified",
)

N_IMAGE_TOKENS = 256
