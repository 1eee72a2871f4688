"""Model FLOPs per trained token, from the configuration file alone.

6 x (matmul parameters one token uses) + 12 x layers x heads x head_dim x
seq_len:
  * the unembedding counts (tied or not); the embedding lookup does not;
  * for a mixture of experts: attention, router and `top_k` experts, no
    capacity padding and no dispatch or combine arithmetic;
  * attention is counted non-causally (every query against every key), the
    usual MFU convention, so a causal kernel can read up to twice its work;
  * recomputation does not count.
The count never depends on how the program implements the model."""
from __future__ import annotations

from bench.model import head_dim, is_moe


def matmul_params_per_token(cfg: dict) -> int:
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = head_dim(cfg)
    attn = d * h * hd * 2 + d * kv * hd * 2           # q, o and k, v
    if is_moe(cfg):
        e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
        ffn = d * e + k * 3 * d * cfg["intermediate_size"]
    else:
        ffn = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + ffn) + d * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * seq_len)
    return 6.0 * matmul_params_per_token(cfg) + attn
