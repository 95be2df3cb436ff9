"""Olmo-Hybrid-7B with masked HLA2 in its linear-attention layers.

[hf: allenai/Olmo-Hybrid-7B config.json]  32 layers in periods of
``linear_attention x 3, full_attention x 1``; d_model=3840, SwiGLU
d_ff=11008, vocab=100352 (untied), RMSNorm eps 1e-6.  Full-attention
layers: 30 heads of 128, kv 30, no rotary embedding (``rope_theta`` is
null).  The linear-attention layers become the HLA paper's masked
second-order drop-in (arXiv 2510.27258 §5.2) at their published head
widths: 30 heads, key 96, value 192, learned per-head decay, chunk 128;
Gated DeltaNet's short convolution, gates and negative eigenvalues are
not part of the drop-in.  Weights are stored in bfloat16, as a serving
deployment holds them.
"""

from ..models.config import HLAConfig, ModelConfig

PATTERN = ("mixer", "mixer", "mixer", "attn")

CONFIG = ModelConfig(
    name="olmo-hybrid-7b",
    n_layers=32,
    d_model=3840,
    n_heads=30,
    n_kv_heads=30,
    d_ff=11008,
    vocab=100352,
    mixer="hla2",
    mlp="swiglu",
    hla=HLAConfig(variant="hla2", chunk=128, decay="learned", d_key=96,
                  d_value=192),
    layer_pattern=PATTERN,
    rope_theta=None,
    norm_eps=1e-6,
    param_dtype="bfloat16",
    remat="full",
)


def reduced():
    """Two periods at a size the CPU holds: d_model 64, 4 heads of 16 in
    the softmax layers, HLA2 key 8 and value 16, vocabulary 128."""
    return CONFIG.replace(
        n_layers=8, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab=128, hla=HLAConfig(variant="hla2", chunk=16, decay="learned",
                                 d_key=8, d_value=16),
        param_dtype="float32", remat="none", dtype="float32",
    )
