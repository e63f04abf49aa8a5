"""Granite-20B (code) — GPT-BigCode-style MQA [arXiv:2405.04324; hf].

52L d_model=6144 48H (MQA kv=1) d_ff=24576 vocab=49152. GELU MLP (not GLU)
per the GPT-BigCode lineage — with gelu the param count lands at ~20B;
swiglu would overshoot to ~28B."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-20b",
    family="dense",
    n_layers=52,
    d_model=6144,
    n_heads=48,
    n_kv_heads=1,
    d_ff=24576,
    vocab=49152,
    rope_theta=1e4,
    mlp_act="gelu",
    source="arXiv:2405.04324; hf",
)
