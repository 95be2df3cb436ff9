"""Jamba-1.5-Large 398B — hybrid Mamba+Attention 1:7, MoE 16e top-2.

[arXiv:2403.19887; hf]  72L d_model=8192 64H (GQA kv=8) d_ff=24576
vocab=65536.  Periods of 8 layers: attention (the configured mixer) at
index 4, the rest Mamba; MoE replaces the dense FFN on every 2nd layer.
"""

from ..models.config import MambaConfig, MoEConfig, ModelConfig

PATTERN = ("mamba",) * 4 + ("mixer",) + ("mamba",) * 3

CONFIG = ModelConfig(
    name="jamba-1.5-large-398b",
    n_layers=72,
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    d_ff=24576,
    vocab=65536,
    mixer="softmax",
    mlp="swiglu",
    moe=MoEConfig(n_experts=16, top_k=2, d_ff=24576, every=2),
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2),
    layer_pattern=PATTERN,
    remat="full",
    # 398B: fp32 master+moments = 4.8 TB (> 256 x 16 GiB).  bf16 storage +
    # bf16 moments is the standard trade at this scale (see DESIGN.md §4).
    param_dtype="bfloat16",
    moment_dtype="bfloat16",
    grad_accum_dtype="bfloat16",
)


def reduced():
    return CONFIG.replace(
        n_layers=8, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96, vocab=128,
        moe=MoEConfig(n_experts=4, top_k=2, d_ff=96, every=2),
        mamba=MambaConfig(d_state=4, d_conv=4, expand=2),
        remat="none", dtype="float32",
    )
