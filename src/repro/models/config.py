"""Architecture configuration (the assigned 10-arch pool + shape sets)."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

VOCAB_PAD = 2048  # pad vocab to a multiple of this for clean TP sharding


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    n_shared: int = 0           # shared (always-on) experts, deepseek-style
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    d_head: int = 64            # mamba2 head dim (P)
    d_conv: int = 4
    expand: int = 2             # d_inner = expand * d_model


@dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | vlm | audio | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # attention features
    qk_norm: bool = False
    sliding_window: int = 0     # gemma3 local layers
    local_global_pattern: int = 0   # N local layers per 1 global (0 = all global)
    rope_theta: float = 10000.0
    # family extras
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    rwkv: RWKVConfig = field(default_factory=RWKVConfig)
    attn_every: int = 0         # zamba2: shared attention block every N ssm layers
    frontend: str = "none"      # none | vit_stub | audio_stub
    n_patches: int = 0          # vlm stub: patch tokens spliced at the front
    d_frontend: int = 0         # stub frontend feature dim
    # numerics / implementation
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    remat: str = "full"         # none | full  (activation checkpoint policy)
    scan_layers: bool = True
    # reference: the flash kernel on a TPU, else the chunked scan (lm.attention_path);
    # or an attention_block impl by name (pallas | interpret | chunked | naive)
    attention_impl: str = "reference"
    # training bits
    max_lr: float = 3e-4

    @property
    def vocab_padded(self) -> int:
        return _round_up(self.vocab_size, VOCAB_PAD)

    @property
    def d_qkv(self) -> int:
        return self.n_heads * self.d_head

    @property
    def d_kv(self) -> int:
        return self.n_kv_heads * self.d_head

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode state ⇒ eligible for long_500k."""
        return self.family in ("ssm", "hybrid")

    def n_params(self) -> int:
        """Approximate parameter count (embeddings included once)."""
        d, L = self.d_model, self.n_layers
        p = self.vocab_padded * d  # embedding
        if not self.tie_embeddings:
            p += self.vocab_padded * d
        per_layer = 0
        if self.family == "ssm":  # rwkv6
            H = d // self.rwkv.head_size
            per_layer = (
                d * d * 4        # r,k,v,o (time mix)
                + d * H          # decay lora-ish (simplified)
                + d * self.d_ff + self.d_ff * d + d * d  # channel mix (k,v,r)
            )
            p += L * per_layer
        elif self.family == "hybrid":  # zamba2
            d_in = self.ssm.expand * d
            H = d_in // self.ssm.d_head
            ssm_layer = (
                d * (2 * d_in + 2 * self.ssm.d_state * (d_in // self.ssm.d_head) + H)
                + d_in * self.ssm.d_conv
                + d_in * d
                + d * self.d_ff * 3
            )
            # crude but close enough for roofline bookkeeping
            n_attn = max(1, L // max(1, self.attn_every))
            attn_layer = d * (self.d_qkv + 2 * self.d_kv) + self.d_qkv * d
            p += L * ssm_layer + n_attn * (attn_layer + 3 * d * self.d_ff)
        else:
            attn = d * (self.d_qkv + 2 * self.d_kv) + self.d_qkv * d
            if self.moe.n_experts:
                mlp = (
                    self.moe.n_experts * 3 * d * self.moe.d_expert
                    + self.moe.n_shared * 3 * d * self.moe.d_expert
                    + d * self.moe.n_experts  # router
                )
            else:
                mlp = 3 * d * self.d_ff
            p += L * (attn + mlp)
        return p

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if not self.moe.n_experts:
            return self.n_params()
        d, L = self.d_model, self.n_layers
        total = self.n_params()
        all_experts = L * self.moe.n_experts * 3 * d * self.moe.d_expert
        active = L * (self.moe.top_k + self.moe.n_shared) * 3 * d * self.moe.d_expert
        return total - all_experts + active

    def replace(self, **kw: Any) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Shapes (assigned to the LM pool — all 10 archs share these four)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def cell_is_supported(cfg: ArchConfig, shape: str) -> tuple[bool, str]:
    """(supported, reason) for an (arch × shape) cell: long_500k only for
    archs with sub-quadratic decode state (ssm, hybrid)."""
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, (
            "long_500k requires sub-quadratic attention; "
            f"{cfg.name} ({cfg.family}) uses full attention"
        )
    return True, ""
