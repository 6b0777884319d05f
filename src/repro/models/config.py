"""Model/run configuration dataclasses + arch registry."""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    experts_per_token: int
    expert_d_ff: int
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3
    # width of a shared SwiGLU expert that every token passes through beside
    # the routed ones (0: none)
    shared_d_ff: int = 0
    # (first, count): the contiguous range of experts this chip holds under
    # expert parallelism. The router keeps all ``num_experts`` outputs and
    # its top-k over them; the layer adds only its held experts' part.
    # None: every expert is held.
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)


@dataclass(frozen=True)
class MambaConfig:
    """Mamba block, TPU-adapted as the Mamba-2/SSD matmul formulation.

    (DESIGN.md §3: scalar-per-head decay — the MXU-friendly reformulation of
    the selective scan; chunked over seq.)
    """

    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256
    conv_bias: bool = False


@dataclass(frozen=True)
class XLSTMConfig:
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    conv_width: int = 4
    slstm_every: int = 4  # every k-th block is sLSTM (rest mLSTM)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | ssm | hybrid | moe | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 → d_model // num_heads
    # block layout: pattern cycled over layers. entries: attn | mamba | slstm | mlstm
    block_pattern: Tuple[str, ...] = ("attn",)
    # MoE: layer i is MoE iff moe_every > 0 and (i % moe_every == moe_offset)
    moe: Optional[MoEConfig] = None
    moe_every: int = 0
    moe_offset: int = 1
    mamba: Optional[MambaConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    # attention details
    mlp_kind: str = "swiglu"  # swiglu | gelu
    norm_kind: str = "rmsnorm"  # rmsnorm | layernorm
    qk_norm: bool = False
    rope_theta: float = 1e4
    rotary_pct: float = 1.0
    attn_logit_softcap: float = 0.0  # grok-style tanh softcap, 0 = off
    # softmax scale of attention logits; None = 1/sqrt(head_dim)
    attention_multiplier: Optional[float] = None
    # µP scalars (Granite): the embedding is multiplied by
    # ``embedding_multiplier``, every sublayer's output by
    # ``residual_multiplier`` before its residual add, and the logits are
    # divided by ``logits_scaling``. None = absent.
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    tie_embeddings: bool = False
    # encoder-decoder
    encoder_decoder: bool = False
    num_encoder_layers: int = 0
    # modality frontend stub: none | audio | vision (precomputed embeddings input)
    frontend: str = "none"
    frontend_seq: int = 0  # frontend embedding positions prepended to the sequence
    max_seq_len: int = 131072
    # numerics
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # lowering scale knobs
    scan_layers: bool = False  # scan over layer periods (compile-time saver)
    remat: str = "block"  # none | block | full

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ---- derived ----
    def block_kind(self, layer: int) -> str:
        return self.block_pattern[layer % len(self.block_pattern)]

    def is_moe_layer(self, layer: int) -> bool:
        return self.moe is not None and self.moe_every > 0 and (
            layer % self.moe_every == self.moe_offset % self.moe_every
        )

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


# ---------------------------------------------------------------- registry
_REGISTRY: dict = {}


def register(name: str, fn):
    _REGISTRY[name] = fn


def get_config(name: str, **overrides) -> ModelConfig:
    import repro.configs  # noqa: F401  (populates registry)

    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    return cfg.with_(**overrides) if overrides else cfg


def list_archs():
    import repro.configs  # noqa: F401

    return sorted(_REGISTRY)
