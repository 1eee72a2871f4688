"""Work of the dropless expert layer's grouped matrix products in one
training step, from the configuration file and the traffic alone, so that
any implementation is read against the same work.

Per layer and replica, T tokens routed to k experts each give M = T x k
rows; three matrices (d -> f gate and up, f -> d down) of E experts each,
and each is used three times: the forward product, the data gradient and
the weight gradient.

  FLOPs  3 matrices x 3 passes x 2 M d f
  bytes  the least each product must move in bfloat16: its two operands
         read once and its result written once; (M, in) rows, the
         (E, in, out) expert matrices, (M, out) rows, in whichever of the
         three roles the pass gives them

Replicas hold their own weights, so the weights count once per replica."""
from __future__ import annotations

BF16 = 2


def _per_layer_replica(cfg: dict, traffic: dict):
    t = traffic["sequences_per_replica"] * traffic["seq_len"]
    m = t * cfg["num_experts_per_tok"]
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return m, d, f, cfg["num_local_experts"]


def _scale(cfg: dict, traffic: dict) -> int:
    return cfg["num_hidden_layers"] * traffic["replicas"]


def flops_per_step(cfg: dict, traffic: dict) -> float:
    m, d, f, _ = _per_layer_replica(cfg, traffic)
    return 3 * 3 * 2.0 * m * d * f * _scale(cfg, traffic)


def bytes_per_step(cfg: dict, traffic: dict) -> float:
    m, d, f, e = _per_layer_replica(cfg, traffic)
    # each pass of each matrix touches its (M, d) rows, its (M, f) rows
    # and its (E, d, f) weights once: as two operands and one result
    per_matrix_pass = m * d + m * f + e * d * f
    return 3 * 3 * BF16 * float(per_matrix_pass) * _scale(cfg, traffic)
