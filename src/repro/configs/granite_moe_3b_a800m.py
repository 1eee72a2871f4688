"""Granite-3.0-3B-A800M [hf:ibm-granite/granite-3.0-3b-a800m-base].

32L d_model=1536 24H (GQA kv=8, head_dim=64) vocab=49155, tied embeddings,
MoE 40 experts top-8, expert d_ff=512, SwiGLU.
Granite routes without dropping tokens (its experts take every (token,
slot) pair, sorted by expert): capacity_factor None, the dropless path.
Its router_aux_loss_coef is 0.001 and it has no router z-loss. Its four
scalar multipliers: embeddings x12, attention scores x1/64 (not
head_dim ** -0.5), each residual branch x0.22, logits / 6.
40 % 16 != 0 -> tensor-parallel expert sharding (per-expert d_ff over "model").
24 heads % 16 != 0 -> projections sharded on the fused dim, not the head axis.
"""
from repro.configs.base import ArchConfig, ATTN, MoEConfig

CONFIG = ArchConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    head_dim=64,
    d_ff=0,
    vocab_size=49155,
    layer_pattern=(ATTN,),
    moe=MoEConfig(n_experts=40, top_k=8, d_ff=512, n_shared_experts=0,
                  capacity_factor=None, sharding="tensor",
                  load_balance_loss=0.001, router_z_loss=0.0),
    rope_theta=10_000.0,
    tie_embeddings=True,
    long_context_window=8192,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
    source="[hf:ibm-granite/granite-3.0-3b-a800m-base]",
)
