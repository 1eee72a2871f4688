"""Architecture configuration system.

Every assigned architecture gets one module in this package exporting CONFIG
(the exact published shape, used only via the ShapeDtypeStruct dry-run) and
reduced() (a tiny same-family variant for CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

# Layer kinds usable in ArchConfig.layer_pattern.
ATTN = "attn"              # global causal attention
ATTN_SWA = "attn_swa"      # sliding-window causal attention
ATTN_LOCAL = "attn_local"  # local attention (recurrentgemma-style window)
MAMBA = "mamba"            # Mamba-1 selective-SSM mixer
RGLRU = "rglru"            # RG-LRU gated linear recurrence mixer

ATTENTION_KINDS = (ATTN, ATTN_SWA, ATTN_LOCAL)
RECURRENT_KINDS = (MAMBA, RGLRU)


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                     # per-expert hidden size
    n_shared_experts: int = 0     # dense "shared expert" branch (DeepSeek-style)
    # per-expert capacity = group tokens x top_k x capacity_factor / experts;
    # None, or a factor at which capacity cannot bind (see `dropless`),
    # routes every (token, slot) pair: the dropless sorted path
    capacity_factor: Optional[float] = 1.25
    # routing group length (GShard "groups"): capacity is allocated per
    # group of this many tokens, bounding the (G, E, C) dispatch tensor to
    # O(group_size^2 * top_k / n_experts) instead of O(seq_len^2 ...).
    group_size: int = 2048
    # "expert": shard the expert axis over the "model" mesh axis (requires
    #           n_experts % model_parallel == 0)
    # "tensor": shard each expert's d_ff over "model" (always valid)
    sharding: str = "expert"
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2

    @property
    def dropless(self) -> bool:
        """True when capacity cannot bind: no factor, or one at which each
        expert's capacity holds every token of the group (the top-k
        experts of a token are distinct, so an expert gets at most one
        pair per token)."""
        return (self.capacity_factor is None
                or self.capacity_factor * self.top_k >= self.n_experts)


@dataclass(frozen=True)
class SSMConfig:  # Mamba-1
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0     # 0 -> d_model
    conv_width: int = 4
    c_exponent: float = 8.0


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | ssm | moe | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                       # dense FFN hidden (0 for attn-free / pure-MoE)
    vocab_size: int
    layer_pattern: Tuple[str, ...] = (ATTN,)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    qk_norm: bool = False
    rope_type: str = "standard"     # standard | mrope | none
    rope_theta: float = 10000.0
    sliding_window: int = 0         # window for attn_swa / attn_local layers
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # Dense archs run long_500k with this window via a sliding-window variant;
    # 0 means the arch is natively sub-quadratic (or attention-free).
    long_context_window: int = 0
    # vlm / audio: input_specs() provides precomputed frontend embeddings of
    # shape (batch, prefix_len, d_model) consumed by the backbone.
    prefix_embed_len: int = 0
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    source: str = ""                # citation bracket from the assignment
    # Granite-style scalar multipliers; None leaves the path as it is:
    # token embeddings x embedding_multiplier, attention scores x
    # attention_multiplier (in place of head_dim ** -0.5), each residual
    # branch x residual_multiplier, logits / logits_scaling
    embedding_multiplier: Optional[float] = None
    attention_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None

    # -- derived ----------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def dt_rank(self) -> int:
        if self.ssm is None:
            return 0
        return self.ssm.dt_rank or -(-self.d_model // 16)

    @property
    def d_inner(self) -> int:
        return 0 if self.ssm is None else self.ssm.expand * self.d_model

    @property
    def lru_width(self) -> int:
        if self.rglru is None:
            return 0
        return self.rglru.lru_width or self.d_model

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def has_attention(self) -> bool:
        return any(k in ATTENTION_KINDS for k in self.layer_pattern)

    def is_subquadratic(self) -> bool:
        """True if no layer attends over unbounded context."""
        return all(
            k in RECURRENT_KINDS or (k in ATTENTION_KINDS and k != ATTN)
            for k in self.layer_pattern
        ) and (self.sliding_window > 0 or not self.has_attention())

    def validate(self) -> None:
        assert self.n_layers >= 1 and self.d_model >= 1
        if self.has_attention():
            assert self.n_heads >= 1 and self.head_dim >= 1
            assert self.n_heads % max(self.n_kv_heads, 1) == 0, self.name
        for k in self.layer_pattern:
            assert k in ATTENTION_KINDS + RECURRENT_KINDS, k
        if MAMBA in self.layer_pattern:
            assert self.ssm is not None
        if RGLRU in self.layer_pattern:
            assert self.rglru is not None
        if any(k in (ATTN_SWA, ATTN_LOCAL) for k in self.layer_pattern):
            assert self.sliding_window > 0, self.name
        if self.moe is not None:
            assert self.moe.top_k <= self.moe.n_experts

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


def reduce_config(cfg: ArchConfig) -> ArchConfig:
    """Tiny same-family variant for CPU smoke tests (<=2 pattern repeats,
    d_model<=256, <=4 experts)."""
    pat = cfg.layer_pattern
    n_layers = len(pat) if len(pat) > 1 else 2
    d_model = min(cfg.d_model, 256)
    n_heads = min(cfg.n_heads, 4)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    while n_heads % n_kv:
        n_kv -= 1
    head_dim = max(8, d_model // max(n_heads, 1))
    moe = cfg.moe
    if moe is not None:
        moe = dataclasses.replace(
            moe, n_experts=min(4, moe.n_experts), top_k=min(2, moe.top_k),
            d_ff=min(64, moe.d_ff),
            n_shared_experts=min(1, moe.n_shared_experts))
    rglru = cfg.rglru
    if rglru is not None:
        rglru = dataclasses.replace(
            rglru, lru_width=min(rglru.lru_width or cfg.d_model, d_model))
    return cfg.replace(
        n_layers=n_layers, d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv,
        head_dim=head_dim, d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512), moe=moe, rglru=rglru,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        long_context_window=min(cfg.long_context_window, 64)
        if cfg.long_context_window else 0,
        prefix_embed_len=min(cfg.prefix_embed_len, 8),
        param_dtype="float32", compute_dtype="float32",
    )


ARCH_IDS = (
    "musicgen-large",
    "falcon-mamba-7b",
    "qwen3-8b",
    "llama3.2-1b",
    "moonshot-v1-16b-a3b",
    "recurrentgemma-9b",
    "granite-moe-3b-a800m",
    "minitron-8b",
    "qwen2-vl-2b",
    "mixtral-8x22b",
    "resnet50",  # the paper's own benchmark model (CNN family)
)


def get_config(arch_id: str) -> ArchConfig:
    mod_name = arch_id.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    cfg = mod.CONFIG
    if isinstance(cfg, ArchConfig):
        cfg.validate()
    return cfg


def get_reduced(arch_id: str):
    mod_name = arch_id.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    if hasattr(mod, "reduced"):
        return mod.reduced()
    return reduce_config(mod.CONFIG)
