"""Model / run configuration dataclasses.

Every assigned architecture gets a module in ``repro/configs/<id>.py`` that
exports ``CONFIG`` (exact, full-size — used only by the dry-run, never
allocated) and ``SMOKE_CONFIG`` (same family, tiny — used by CPU tests).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                      # per-expert FFN hidden dim
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style: mamba2 backbone + weight-shared attention block."""
    attn_every: int = 6                # shared attn after every N mamba layers


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                        # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None     # default d_model // num_heads
    mixer: str = "attention"           # attention | mla | mamba2 | rwkv6
    mlp: str = "swiglu"                # swiglu | gelu
    rope: str = "standard"             # standard | 2d | mrope | none
    rope_theta: float = 1e4
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    hybrid: Optional[HybridConfig] = None
    ssm_state: int = 0                 # mamba2 state size N
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_kernel: int = 4
    encdec: bool = False               # whisper
    enc_layers: int = 0
    tie_embeddings: bool = False
    attn_bias: bool = False            # starcoder2/whisper use biases
    # the model's published sliding attention window (tokens), or None for
    # full causal attention; no kernel masks behind a window, so the
    # serving engine refuses a max_seq that would reach past it
    sliding_window: Optional[int] = None
    dtype: str = "bfloat16"
    # long-context support marker (sub-quadratic mixer): set per-arch
    subquadratic: bool = False
    # beyond-paper: int8 KV cache (decode path) — halves cache HBM traffic
    kv_quant: bool = False
    # attention impl for full-sequence paths: "chunked" (jnp online-softmax,
    # CPU/dry-run lowerable) | "flash" (Pallas kernel w/ causal block-skip,
    # real-TPU; interpret-mode in tests)
    attn_impl: str = "chunked"
    # paged decode attention impl: "auto" (Pallas kernel when the backend is
    # pallas / a TPU, jnp gather otherwise) | "gather" (jnp page gather — the
    # XLA reference and oracle) | "pallas" (fused page-table-DMA kernel,
    # real-TPU) | "pallas_interpret" (same kernel interpreted on CPU, tests)
    paged_attn_impl: str = "auto"
    # activation quantization: "a16" (bf16/f32 activations everywhere — the
    # default, token-identical to the pre-W4A8 engine) | "a8_prefill"
    # (prefill-chunk GEMMs quantize activations per-token to int8 and run the
    # int8×int4→int32 kernel body on A8-eligible layers; decode GEMMs stay
    # A16 via the token-count gate in kernels.ops)
    act_quant: str = "a16"

    @property
    def hdim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def act_kernel(self) -> str:
        """The per-call ``act=`` value model code hands to ``kernels.ops`` —
        the ops-level gate (per-tensor eligibility flag + token count)
        decides whether the A8 body actually runs."""
        return "a8" if self.act_quant == "a8_prefill" else "a16"

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned (input-shape) cell."""
    name: str                          # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", "train", 4_096, 256),
    ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    ShapeConfig("decode_32k", "decode", 32_768, 128),
    ShapeConfig("long_500k", "decode", 524_288, 1),
)

SHAPES_BY_NAME = {s.name: s for s in SHAPES}


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    enabled: bool = True
    group_size: int = 128
    # layers excluded from quantization (paper keeps embeddings/norms fp16;
    # we also keep lm_head and MoE routers in bf16, matching common practice)
    skip_lm_head: bool = True
    skip_router: bool = True
    alpha: Optional[float] = None      # None → use searched value
    backend: str = "auto"              # kernels.ops backend
    # W4A8 eligibility: layers whose worst per-token int8 activation
    # round-trip error (post-smoothing, on the calibration set) exceeds this
    # fall back to A16 in the prefill path.  Gaussian-ish rows score
    # ~1/(127·√12) ≈ 0.7–0.9%; rows still dominated by surviving outlier
    # channels score 2%+.  Part of the PTQ artifact fingerprint, so changing
    # it invalidates saved artifacts.
    a8_threshold: float = 0.015


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    microbatch: Optional[int] = None   # per-device microbatching (grad accum)
    remat: str = "block"               # none | block | full
    zero_sharded_optimizer: bool = True
    grad_compression: str = "none"     # none | int8_ef
