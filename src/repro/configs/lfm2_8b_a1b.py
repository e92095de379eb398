"""LFM2-8B-A1B (LiquidAI, edge MoE).  [hf:LiquidAI/LFM2-8B-A1B; hf] -
24L d_model=2048: 18 gated short-conv layers (3 taps) and 6 GQA layers
(32 q / 8 kv heads, head_dim 64, q/k RMSNorm, rope_theta 1e6) at 2, 6,
10, 14, 18, 21; the first 2 layers dense SwiGLU (7168), then 32 experts
of width 1792, top-4 by sigmoid score plus a selection bias, weights
normalized over the chosen 4; vocab 65536, head tied to the embedding.

``FULL`` holds every expert; ``SMOKE`` keeps the first 6 layers' pattern
(2 dense conv, attention, 3 conv, all MoE after the first two) at d=128
with 8 experts of which one chip holds 4."""
from repro.configs.base import ArchConfig

_TYPES = ("conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv",
          "conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv",
          "conv", "conv", "attn", "conv", "conv", "attn", "conv", "conv")
N_DENSE = 2


def layer_kinds(n_layers: int, types=_TYPES, n_dense: int = N_DENSE):
    """The per-layer kinds of the first ``n_layers`` layers."""
    return tuple(f"{t}_{'mlp' if i < n_dense else 'moe'}"
                 for i, t in enumerate(types[:n_layers]))


FULL = ArchConfig(
    name="lfm2-8b-a1b", family="hybrid", n_layers=24, d_model=2048,
    n_heads=32, n_kv_heads=8, head_dim=64, d_ff=7168, vocab_size=65536,
    tie_embeddings=True, rope_theta=1e6, norm="rmsnorm", act="swiglu",
    layer_kinds=layer_kinds(24), conv_taps=3, qk_norm=True,
    n_experts=32, top_k=4, moe_d_ff=1792,
    held_experts=32,
    source="hf:LiquidAI/LFM2-8B-A1B; hf",
)

SMOKE = ArchConfig(
    name="lfm2-8b-a1b-smoke", family="hybrid", n_layers=6, d_model=128,
    n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512,
    rope_theta=1e6, norm="rmsnorm", act="swiglu",
    layer_kinds=layer_kinds(6), conv_taps=3, qk_norm=True,
    n_experts=8, top_k=4, moe_d_ff=64, held_experts=4,
)
