"""Model configuration schema + registry for the assigned architectures."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

ARCH_IDS = (
    "whisper-small", "grok-1-314b", "granite-moe-3b-a800m", "llava-next-34b",
    "olmo-1b", "qwen3-8b", "codeqwen1.5-7b", "qwen2-7b", "mamba2-780m",
    "recurrentgemma-2b", "lfm2-8b-a1b",
)

_MODULES = {
    "whisper-small": "whisper_small",
    "grok-1-314b": "grok1_314b",
    "granite-moe-3b-a800m": "granite_moe_3b",
    "llava-next-34b": "llava_next_34b",
    "olmo-1b": "olmo_1b",
    "qwen3-8b": "qwen3_8b",
    "codeqwen1.5-7b": "codeqwen15_7b",
    "qwen2-7b": "qwen2_7b",
    "mamba2-780m": "mamba2_780m",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "lfm2-8b-a1b": "lfm2_8b_a1b",
}


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int             # the router's outputs: every expert
    top_k: int
    # "softmax" over the top-k logits, or "sigmoid" scores (lfm2): the top k
    # of score + a learned per-expert bias (it picks, it never weighs), their
    # scores normalised to sum 1
    router: str = "softmax"
    # expert parallelism: this chip holds experts [first_held, first_held +
    # held) and computes their part of the layer, dropless, routed by
    # sigmoid; None holds all of them behind the capacity dispatch
    held: Optional[int] = None
    first_held: int = 0

    def __post_init__(self):
        if (self.router == "sigmoid") != (self.held is not None):
            raise ValueError("sigmoid routing goes with a held share of the "
                             "experts: the capacity dispatch routes by "
                             "softmax")


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64          # P per head; heads = d_inner / head_dim
    expand: int = 2
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str                  # dense | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    num_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    # variants
    qk_norm: bool = False
    qkv_bias: bool = False
    non_parametric_ln: bool = False        # olmo
    norm: str = "rmsnorm"                  # rmsnorm | layernorm
    norm_eps: Optional[float] = None       # None: 1e-5 layernorm, 1e-6 rmsnorm
    activation: str = "silu"               # silu(swiglu) | gelu
    glu: bool = True
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid: pattern period of mixer kinds, "attn" | "rglru" | "conv" —
    # ("rglru","rglru","attn") for recurrentgemma, every layer for lfm2
    block_pattern: Optional[Tuple[str, ...]] = None
    conv_width: int = 4                    # short causal conv (lfm2: 3)
    # hybrid with moe: the first num_dense_layers take a dense MLP of width
    # dense_d_ff, the rest experts of width d_ff
    num_dense_layers: int = 0
    dense_d_ff: Optional[int] = None
    window: Optional[int] = None           # local attention window
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_frames: int = 0                # stub frontend sequence length
    # vlm
    num_prefix_embeds: int = 0             # stub patch-embedding prefix
    max_seq_len: int = 524288

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // max(self.num_heads, 1)

    @property
    def resolved_norm_eps(self) -> float:
        if self.norm_eps is not None:
            return self.norm_eps
        return 1e-5 if self.norm == "layernorm" else 1e-6

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch serve 500k-token contexts sub-quadratically?"""
        return self.family == "ssm" or (self.family == "hybrid"
                                        and self.window is not None)

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw: Dict = dataclasses.asdict(self)
        kw.update(
            num_layers=min(self.num_layers, 2),
            d_model=128,
            num_heads=4,
            kv_heads=min(max(1, self.kv_heads * 4 // max(self.num_heads, 1)), 4),
            d_ff=256,
            vocab=512,
            head_dim=32,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_frames=min(self.encoder_frames, 16) if self.encoder_frames else 0,
            num_prefix_embeds=min(self.num_prefix_embeds, 8),
            window=min(self.window, 32) if self.window else None,
            max_seq_len=2048,
        )
        if self.moe and self.moe.held is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=min(self.moe.num_experts, 8),
                top_k=min(self.moe.top_k, 2), held=min(self.moe.held, 4),
                first_held=0)
        elif self.moe:
            kw["moe"] = MoEConfig(num_experts=min(self.moe.num_experts, 4),
                                  top_k=min(self.moe.top_k, 2))
        else:
            kw["moe"] = None
        if self.dense_d_ff:
            kw["dense_d_ff"] = 384
        if self.ssm:
            kw["ssm"] = SSMConfig(d_state=32, head_dim=16, expand=2, chunk=32)
        else:
            kw["ssm"] = None
        if self.block_pattern:
            kw["block_pattern"] = tuple(self.block_pattern)
            kw["num_layers"] = min(self.num_layers, 3)
        return ModelConfig(**kw)


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
