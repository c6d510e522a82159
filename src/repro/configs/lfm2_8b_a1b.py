"""lfm2-8b-a1b [hybrid]: 24L d_model=2048; 18 gated short-conv layers
(width 3) and 6 GQA attention layers (32H over 8 KV heads, head 64, qk-norm,
RoPE 1e6); the first 2 layers take a dense SwiGLU of width 7168, the other
22 sparse experts: 32 of width 1792, top-4 by sigmoid score + expert bias,
normalised, scale 1; vocab 65536, RMSNorm eps 1e-5, tied embeddings.
[hf:LiquidAI/LFM2-8B-A1B config.json]

Serving deployment: four chips share each MoE layer by expert parallelism,
8 experts each; everything else is whole on every chip. This chip holds
experts 0-7 of every MoE layer, and its router keeps all 32 outputs."""
from repro.configs.base import ModelConfig, MoEConfig

LAYER_TYPES = ("conv", "conv", "attn", "conv", "conv", "conv", "attn",
               "conv", "conv", "conv", "attn", "conv", "conv", "conv", "attn",
               "conv", "conv", "conv", "attn", "conv", "conv", "attn", "conv",
               "conv")

CONFIG = ModelConfig(
    arch="lfm2-8b-a1b", family="hybrid",
    num_layers=24, d_model=2048, num_heads=32, kv_heads=8, head_dim=64,
    d_ff=1792, vocab=65536, qk_norm=True, norm_eps=1e-5,
    rope_theta=1e6, tie_embeddings=True, block_pattern=LAYER_TYPES,
    conv_width=3, num_dense_layers=2, dense_d_ff=7168,
    moe=MoEConfig(num_experts=32, top_k=4, router="sigmoid", held=8,
                  first_held=0),
    max_seq_len=128000,
)
