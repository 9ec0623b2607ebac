"""Config schema: architectures, shapes, parallelism and balancer knobs.

Every assigned architecture is a :class:`ModelConfig` built in its own
``configs/<id>.py`` file and registered here.  Shapes (train_4k /
prefill_32k / decode_32k / long_500k) are global and filtered per-arch by
``shape_skips``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

__all__ = ["MoEArch", "SSMArch", "ModelConfig", "ShapeSpec", "SHAPES",
           "register", "get_config", "list_archs", "layer_kinds"]


@dataclasses.dataclass(frozen=True)
class MoEArch:
    num_experts: int
    top_k: int
    d_ff: int                       # per-expert hidden
    score_fn: str = "softmax"
    norm_topk_prob: bool = True
    aux_loss_weight: float = 1e-2   # GShard loss (0 = disabled)
    use_bias: bool = False          # DeepSeek aux-free bias router
    bias_update_speed: float = 1e-3
    routed_scaling: float = 1.0
    n_shared_experts: int = 0
    shared_d_ff: int = 0
    layer_period: int = 1           # MoE every k-th layer (jamba: 2)
    first_dense_layers: int = 0     # leading dense-FFN layers (deepseek: 3)
    n_slot: int = 2                 # redundant slots per rank (Table 3)
    # Expert share: the block of the router's experts this deployment's
    # chip holds, ``held_experts`` of them from ``first_expert`` (0 = all).
    # The router keeps its width; pairs routed to experts held elsewhere
    # are not computed here (moe/gating.py).
    held_experts: int = 0
    first_expert: int = 0

    def __post_init__(self):
        if not (0 <= self.first_expert and 0 <= self.held_experts
                and self.first_expert + self.held <= self.num_experts):
            raise ValueError(
                f"held block [{self.first_expert}, {self.first_expert} + "
                f"{self.held}) lies outside the router's "
                f"{self.num_experts} experts")

    @property
    def held(self) -> int:
        """Experts this chip holds of each MoE layer."""
        return self.held_experts or self.num_experts

    @property
    def holds_share(self) -> bool:
        """True when the chip holds only a block of the router's experts."""
        return self.held < self.num_experts


@dataclasses.dataclass(frozen=True)
class SSMArch:
    d_inner: int
    d_state: int = 128
    headdim: int = 64
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 128
    attn_period: int = 0            # hybrid: attention every k-th layer
    attn_offset: int = 0            # ...at i % period == offset (jamba: 4)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    vocab_size: int
    # attention
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    qk_norm: bool = False
    causal: bool = True
    rope_theta: float = 10000.0
    # MLA (deepseek)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # dense FFN hidden (non-MoE layers)
    d_ff: int = 0
    moe: MoEArch | None = None
    ssm: SSMArch | None = None
    # modality frontend stub ("none" | "audio_frames" | "vision_patches")
    frontend: str = "none"
    num_patches: int = 256          # vlm stub prefix length
    tie_embeddings: bool = False
    shape_skips: tuple[str, ...] = ()
    # citation / provenance
    source: str = ""

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def has_decode(self) -> bool:
        return self.causal  # encoder-only archs have no decode step


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str        # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def layer_kinds(cfg: ModelConfig) -> list[str]:
    """Per-layer block kind: '<mixer>+<ffn>' with mixer in {attn, mamba}
    and ffn in {dense, moe, none}."""
    kinds = []
    for i in range(cfg.num_layers):
        if cfg.ssm is not None:
            is_attn = (
                cfg.ssm.attn_period > 0
                and i % cfg.ssm.attn_period == cfg.ssm.attn_offset
            )
            mixer = "attn" if is_attn else "mamba"
        else:
            mixer = "attn"
        if cfg.moe is not None:
            if i < cfg.moe.first_dense_layers:
                ffn = "dense"
            elif (i % cfg.moe.layer_period) == (cfg.moe.layer_period - 1) or \
                    cfg.moe.layer_period == 1:
                ffn = "moe"
            else:
                ffn = "dense"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = "none"   # pure mamba blocks carry no separate FFN
        kinds.append(f"{mixer}+{ffn}")
    return kinds


_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # Import the arch modules lazily so registration side-effects run.
        import repro.configs  # noqa: F401  (populates the registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch '{name}'; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list[str]:
    import repro.configs  # noqa: F401

    return sorted(_REGISTRY)
