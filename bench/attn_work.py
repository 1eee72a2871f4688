"""Work of causal self-attention in one training step, forward and
backward, from the configuration file and the traffic alone: a lower
bound on what any implementation does, so that its roofline share stays
under 100% whatever its blocking and recomputation.

Per layer, replica and sequence of S tokens, Hq query heads of head_dim
d and Hk key/value heads:

  FLOPs  6 products x 2 x Hq x d x S (S + 1) / 2: the forward pass's
         scores and P.V, the backward pass's dP, dV, dQ and dK, over the
         causal triangle only, nothing recomputed
  bytes  bfloat16, each tensor once: q, k, v, o and dO read, o, dq, dk
         and dv written: S d (5 Hq + 4 Hk) elements"""
from __future__ import annotations

from bench.model import head_dim

BF16 = 2


def _scale(cfg: dict, traffic: dict) -> int:
    return (cfg["num_hidden_layers"] * traffic["replicas"]
            * traffic["sequences_per_replica"])


def flops_per_step(cfg: dict, traffic: dict) -> float:
    s, hq, d = traffic["seq_len"], cfg["num_attention_heads"], head_dim(cfg)
    return 6 * 2.0 * hq * d * s * (s + 1) / 2 * _scale(cfg, traffic)


def bytes_per_step(cfg: dict, traffic: dict) -> float:
    s, d = traffic["seq_len"], head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return BF16 * float(s * d * (5 * hq + 4 * hk)) * _scale(cfg, traffic)
