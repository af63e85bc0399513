"""Config schema for every architecture + the four benchmark input shapes."""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp

from repro.core.lords import QuantSpec

__all__ = [
    "MoECfg", "MLACfg", "YarnCfg", "MambaCfg", "XLSTMCfg", "ModelConfig",
    "ShapeCfg", "SHAPES", "register", "get_config", "list_configs",
]


@dataclasses.dataclass(frozen=True)
class MoECfg:
    num_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden
    capacity_factor: float = 1.25
    router_dtype: Any = jnp.float32
    every: int = 1                 # MoE layer every `every` layers (jamba: 2)
    # expert-parallel dispatch implementation:
    #   pjit      — scatter/gather + GSPMD-inferred collectives (portable)
    #   shard_map — explicit local dispatch + all_to_all over the EP axes
    #               (the §Perf fix for collective-bound MoE training)
    dispatch: str = "pjit"
    pad_experts_to: int | None = None  # pad so EP divides the device count
    # DeepSeek-style settings; None means "not set" and keeps the legacy
    # behaviour (no shared experts, no leading dense layers, softmax top-k
    # with the gates renormalised to sum to 1)
    n_shared: int | None = None        # shared experts: one SwiGLU of n·d_ff
    first_dense: int | None = None     # leading dense layers (d_ff wide)
    scoring_func: str | None = None    # router scores: 'softmax'
    norm_topk_prob: bool | None = None  # renormalise the top-k gates
    routed_scaling_factor: float | None = None  # gate scale if not renormed
    topk_method: str | None = None     # 'greedy' (plain top-k)
    n_group: int | None = None         # expert groups (1: ungrouped)
    topk_group: int | None = None      # groups a token may use

    def __post_init__(self):
        if self.scoring_func not in (None, "softmax"):
            raise ValueError(f"router scoring {self.scoring_func!r}: only "
                             "softmax is implemented")
        if self.topk_method not in (None, "greedy") or (
                self.n_group or 1) != 1 or (self.topk_group or 1) != 1:
            raise ValueError("only greedy top-k over one expert group is "
                             "implemented")

    @property
    def renormalise(self) -> bool:
        return self.norm_topk_prob is not False

    @property
    def shared_ff(self) -> int:
        """Width of the shared-expert SwiGLU (0: none)."""
        return (self.n_shared or 0) * self.d_ff


@dataclasses.dataclass(frozen=True)
class MLACfg:
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class YarnCfg:
    """YaRN rope scaling (DeepSeek-V2's ``rope_scaling`` of type yarn):
    inverse frequencies blended between interpolated (÷ factor) and
    original by a linear ramp over the dims whose wavelength lies between
    ``beta_fast`` and ``beta_slow`` rotations of the original context; the
    attention softmax scale multiplied by ``mscale(mscale_all_dim)²`` and
    cos/sin by ``mscale(mscale) / mscale(mscale_all_dim)``, where
    ``mscale(m) = 0.1·m·ln(factor) + 1``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def published(self) -> dict:
        return {"beta_fast": self.beta_fast, "beta_slow": self.beta_slow,
                "factor": self.factor, "mscale": self.mscale,
                "mscale_all_dim": self.mscale_all_dim,
                "original_max_position_embeddings":
                    self.original_max_position_embeddings,
                "type": "yarn"}


@dataclasses.dataclass(frozen=True)
class MambaCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int | None = None     # default ceil(d_model / 16)
    chunk: int = 128               # chunked associative scan length


@dataclasses.dataclass(frozen=True)
class XLSTMCfg:
    proj_factor: float = 2.0
    conv_k: int = 4
    slstm_every: int = 8           # sLSTM block every N layers (rest mLSTM)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | vlm | ssm | audio | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int                      # dense-MLP hidden (0 => none, e.g. xLSTM)
    vocab_size: int
    head_dim: int | None = None    # default d_model // num_heads
    attn_kind: str = "gqa"         # gqa | mla
    mla: MLACfg | None = None
    moe: MoECfg | None = None
    mamba: MambaCfg | None = None
    xlstm: XLSTMCfg | None = None
    # per-layer mixer pattern, tiled over num_layers.
    #   e.g. jamba: ('attn','mamba','mamba','mamba',...)  period 8
    #        xlstm: ('mlstm',)*7 + ('slstm',)
    layer_pattern: tuple = ("attn",)
    rope_theta: float = 500000.0
    rope_scaling: YarnCfg | None = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    input_kind: str = "tokens"     # tokens | embeddings (vlm/audio stubs)
    quant: QuantSpec = QuantSpec(method="lords", codebook="nf4",
                                 block_size=128, mode="peft")
    # decode KV-cache storage: 'bf16' (dense) or 'int8' (per-head symmetric
    # int8 + f32 scales — ~2x less cache HBM traffic per decoded token)
    kv_cache_dtype: str = "bf16"
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "nothing"  # nothing | dots (checkpoint dot outputs)
    vocab_pad_multiple: int = 2048
    micro_tokens: int = 8192       # per-device live tokens per microbatch
    notes: str = ""

    # ---- derived ----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return int(math.ceil(self.vocab_size / m) * m)

    @property
    def lead_layers(self) -> int:
        """Leading dense layers (attention + a d_ff SwiGLU), kept out of
        the scan over periods; the periods cover the layers after them."""
        return (self.moe.first_dense or 0) if self.moe is not None else 0

    @property
    def pattern(self) -> tuple:
        """Full per-layer mixer pattern of length num_layers (tiled)."""
        p = self.layer_pattern
        reps = math.ceil(self.num_layers / len(p))
        return (p * reps)[: self.num_layers]

    @property
    def period(self) -> int:
        """Scan period: LCM of mixer pattern and MoE interleave."""
        p = len(self.layer_pattern)
        if self.moe is not None and self.moe.every > 1:
            p = math.lcm(p, self.moe.every)
        scanned = self.num_layers - self.lead_layers
        if scanned % p:
            # fall back to unrolled if the pattern doesn't tile evenly
            return scanned
        return p

    @property
    def num_periods(self) -> int:
        return (self.num_layers - self.lead_layers) // self.period

    def layer_kinds(self, period_idx: int = 0) -> list[tuple[str, str]]:
        """[(mixer_kind, mlp_kind)] for one scan period."""
        out = []
        for i in range(self.period):
            layer = self.lead_layers + period_idx * self.period + i
            mixer = self.pattern[i % len(self.pattern)]
            if self.moe is not None and layer % self.moe.every == (self.moe.every - 1 if self.moe.every > 1 else 0):
                mlp = "moe"
            elif self.d_ff > 0:
                mlp = "dense"
            else:
                mlp = "none"
            out.append((mixer, mlp))
        return out

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeCfg:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeCfg("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCfg("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCfg("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCfg("long_500k", 524288, 1, "decode"),
}


_REGISTRY: dict[str, Any] = {}


def _norm(name: str) -> str:
    return name.lower().replace("-", "").replace("_", "").replace(".", "")


def register(fn):
    """Decorator: configs/archs.py registers a zero-arg builder."""
    _REGISTRY[_norm(fn.__name__.removesuffix("_cfg"))] = fn
    return fn


def get_config(name: str) -> ModelConfig:
    import repro.configs  # noqa: F401  (triggers submodule registration)

    key = _norm(name)
    if key not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[key]()


def list_configs() -> list[str]:
    import repro.configs  # noqa: F401

    return sorted(cfg().name for cfg in _REGISTRY.values())
