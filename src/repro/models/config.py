"""Model configuration dataclasses (the framework's config system)."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden size
    capacity_factor: float = 1.25
    every: int = 1  # every-th layer is MoE (jamba: 2); 1 = all layers
    aux_loss_coef: float = 0.01


@dataclasses.dataclass(frozen=True)
class HLAConfig:
    """Options for the paper's mixer (Sections 3-7)."""

    variant: str = "hla2"  # hla2 | ahla | hla3 | hla3_paper | linattn
    impl: str = "chunkwise"  # chunkwise (TPU-adapted) | scan (paper-faithful
    #   token-level Blelloch associative scan; the §Perf baseline)
    chunk: int = 256  # §Perf sweep: 256 beats 128/64 on the memory term
    #   (state carry I/O amortizes over the chunk; VMEM-bounded on TPU)
    normalize: bool = False  # paper default: unnormalized
    decay: str = "learned"  # none | fixed | learned  (per-head sigmoid)
    fixed_gamma: float = 0.99
    lam: float = 0.0  # ridge (Alg 1)
    share_kv_state: bool = False  # §5.2 MQA/GQA S^K sharing
    use_pallas: bool = True  # fused kernel on TPU; jnp path on CPU
    fused_bwd: bool = True  # fused Pallas backward with chunk-level state
    #   checkpointing (DESIGN.md §3); False = legacy recompute-in-backward
    #   (second unfused forward under jax.vjp — slower, slightly less HBM)
    force_pallas: bool = False  # run the Pallas kernels in interpret mode
    #   on any backend — how the CPU tests exercise the shard_map'd fused
    #   path on host devices; never set on the chip
    d_key: int = 0  # per-head query/key width; 0 => the model's head_dim
    d_value: int = 0  # per-head value/output width; 0 => head_dim


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 => d_model // 16


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0  # 0 => d_model // n_heads
    mixer: str = "softmax"  # softmax | hla2 | ahla | hla3 | linattn | rwkv6
    mlp: str = "swiglu"  # swiglu | squared_relu | gelu
    moe: Optional[MoEConfig] = None
    hla: HLAConfig = dataclasses.field(default_factory=HLAConfig)
    mamba: Optional[MambaConfig] = None
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: Optional[float] = 1e4  # None: no rotary embedding
    norm_eps: float = 1e-5
    # hybrid stacks: the sequence op at each position of a repeating
    # period of layers, by registered name, with "mixer" standing for
    # ``mixer`` (so a mixer override swaps those positions).  Jamba: 4
    # mamba, "mixer", 3 mamba; Olmo Hybrid: 3 x "mixer", "attn".  Every
    # ``moe.every``-th layer of a period carries an MoE FFN.  () = a
    # uniform stack of ``mixer``.
    layer_pattern: Tuple[str, ...] = ()
    # encoder-decoder (whisper): enc_layers > 0 activates the encoder
    enc_layers: int = 0
    enc_frames: int = 1500  # precomputed frame embeddings (stub frontend)
    # vlm: number of precomputed patch-embedding tokens (stub frontend)
    vis_tokens: int = 0
    # rwkv6
    rwkv_head_dim: int = 64
    # numerics / runtime
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"  # storage dtype (jamba-scale: bfloat16)
    moment_dtype: str = "float32"  # AdamW mu/nu (jamba-scale: bfloat16)
    grad_accum_dtype: str = "float32"  # microbatch grad accumulator
    gather_dtype: str = "float32"  # layer-scan param gathers (bf16 = half
    #   the FSDP all-gather bytes; §Perf lever A)
    remat: str = "none"  # none | full | dots
    scan_layers: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // max(1, self.n_heads))

    def hla_widths(self) -> Tuple[int, int]:
        """(key, value) widths per head of the HLA mixer."""
        return (self.hla.d_key or self.head_dim,
                self.hla.d_value or self.head_dim)

    @property
    def attn_free(self) -> bool:
        return self.mixer in ("rwkv6",)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """An assigned (input-shape) cell."""

    name: str  # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4096, 256, "train"),
    ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    ShapeConfig("decode_32k", 32768, 128, "decode"),
    ShapeConfig("long_500k", 524288, 1, "decode"),
)


def get_shape(name: str) -> ShapeConfig:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
