"""Model/run configuration dataclasses and the architecture registry."""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    renorm_gates: bool = True
    #: every `interleave_step`-th layer is MoE (1 = all layers);
    #: offset chooses which residue is MoE.
    interleave_step: int = 1
    interleave_offset: int = 0
    #: first `first_dense` layers use a dense FFN instead (DeepSeek).
    first_dense: int = 0
    d_ff_first_dense: int = 0
    #: expert-parallel dispatch: "gspmd" hands the token all-to-all to the
    #: partitioner; "rma" runs the sort-based dispatch inside shard_map over
    #: the expert axis through the one-sided declared-usage collective
    #: (repro.core.rma.alltoall; see docs/moe_ep.md).
    ep_mode: str = "gspmd"
    #: lowering backend for the ``ep_mode="rma"`` dispatch/combine plans:
    #: "rma" (the substrate), "gspmd" (recognized macros collapse to
    #: lax.all_to_all), or "auto" (calibrated cost-model pick); the
    #: host-side "interpret" target is invalid inside a mesh.
    ep_backend: str = "rma"
    #: the contiguous expert ids ``(first, stop)`` whose weights this chip
    #: holds (``None``: all of them).  The router keeps every expert and
    #: its top-k over all of them; the layer adds only the held experts'
    #: part of the routed sum, with no capacity drop (``moe.py``).
    experts_held: tuple[int, int] | None = None

    @property
    def n_held(self) -> int:
        if self.experts_held is None:
            return self.num_experts
        first, stop = self.experts_held
        return stop - first

    def capacity(self, tokens: int) -> int:
        c = math.ceil(tokens * self.top_k * self.capacity_factor / self.num_experts)
        return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    #: 0: no query LoRA, the query is projected straight from x (``w_q``)
    q_lora: int = 1536
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling as DeepSeek-V2 publishes it (``rope_scaling`` with
    ``type: yarn``); ``models/layers.py`` applies it."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    headdim: int = 64
    expand: int = 2
    chunk: int = 64
    d_conv: int = 4


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0          # 0 -> d_model // n_heads
    rope_theta: float = 10000.0
    rope_scaling: YarnConfig | None = None
    qk_norm: bool = False
    attn_bias: bool = False
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    act: str = "swiglu"        # swiglu | gelu
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    max_seq: int = 8192
    #: S_q*S_k above which online-softmax scan attention replaces materialized
    #: scores.  2048² = flash-style attention for every production shape
    #: (§Perf iteration 0 quantifies the win over materializing at 4k).
    blockwise_threshold: int = 2048 * 2048
    #: attention implementation: "auto" (full/blockwise by threshold),
    #: "full" (materialized), "blockwise" (scan), or "stub" (projections
    #: only, no quadratic part — used to ISOLATE attention traffic when
    #: modelling the Pallas flash kernel's roofline in §Perf).
    attn_impl: str = "auto"
    attn_block_kv: int = 1024
    # hybrid (jamba): layer i is attention iff i % hybrid_period == hybrid_attn_offset
    hybrid_period: int = 0
    hybrid_attn_offset: int = 0
    # enc-dec (whisper)
    enc_layers: int = 0
    # vlm stub: number of prefix positions fed as precomputed patch embeddings
    vlm_prefix: int = 0
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "block"       # none | block
    #: sub-quadratic decode memory (SSM/hybrid) — eligible for long_500k
    subquadratic: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def vocab_padded(self) -> int:
        """Embedding/LM-head allocation size: vocab padded to a multiple of
        256 so the vocab dim shards evenly over any axis up to 256.  Logit
        pad lanes are masked to -inf, never sliced (keeps output shardings
        even).  The *logical* vocab stays ``self.vocab``."""
        return -(-self.vocab // 256) * 256

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def parameter_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ModelConfig:
    import repro.configs.archs  # noqa: F401  (populates the registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list[str]:
    import repro.configs.archs  # noqa: F401
    return sorted(_REGISTRY)


def cell_is_runnable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether (arch, shape) is a runnable dry-run cell, and why not if not."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, ("pure full-attention architecture: 512k-token decode "
                       "requires sub-quadratic attention (documented skip)")
    return True, ""


__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "SSMConfig", "YarnConfig",
    "ShapeConfig", "SHAPES",
    "register", "get_config", "list_archs", "cell_is_runnable",
]
