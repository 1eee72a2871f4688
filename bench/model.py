"""A configuration file (Hugging Face `config.json` keys, as run) turned
into the program's `ArchConfig`, and the shape facts the yardstick needs."""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["assumed"].get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def is_moe(cfg: dict) -> bool:
    return int(cfg.get("num_local_experts") or 0) > 0


def arch_config(cfg: dict):
    """The program's ArchConfig for this configuration file."""
    from repro.configs.base import ATTN, ArchConfig, MoEConfig
    moe = None
    if is_moe(cfg):
        r = cfg["routing"]
        moe = MoEConfig(n_experts=cfg["num_local_experts"],
                        top_k=cfg["num_experts_per_tok"],
                        d_ff=cfg["intermediate_size"],
                        capacity_factor=r["capacity_factor"],
                        group_size=r["group_size"],
                        load_balance_loss=r["load_balance_loss"],
                        router_z_loss=r["router_z_loss"],
                        sharding="tensor")
    if cfg.get("sliding_window"):
        raise ValueError("sliding-window configurations are not supported "
                         "by this harness")
    arch = ArchConfig(
        name=cfg["model_type"], family="moe" if moe else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=0 if moe else cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], layer_pattern=(ATTN,), moe=moe,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        source=cfg["source"])
    arch.validate()
    return arch
