"""smollm-360m [dense] — llama-arch small [hf:HuggingFaceTB/SmolLM-360M].

15 heads (non-128-aligned head count): attention weights replicate on the
model axis (heads→None sharding fallback) — exercised deliberately.
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="smollm-360m",
    family="dense",
    n_layers=32,
    d_model=960,
    n_heads=15,
    n_kv_heads=5,
    d_head=64,
    d_ff=2560,
    vocab_size=49152,
    tie_embeddings=True,
    rope_theta=10000.0,
)
