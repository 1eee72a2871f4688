"""bench/flops.py against hand counts of the two configurations."""
import pytest

from bench import flops
from bench.tests.helpers import load


def test_mistral_hand_count():
    cfg = load("../../configs/mistral-7b-v0.3.json")
    # per layer: q, o 4096x4096 each; k, v 4096x1024 each; SwiGLU 3 x
    # 4096x14336; plus the untied 4096x32768 LM head (no embedding)
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    mlp = 3 * 4096 * 14336
    head = 4096 * 32768
    assert flops.matmul_params_per_token(cfg) == attn + mlp + head
    assert flops.matmul_params_per_token(cfg) == pytest.approx(352.3e6,
                                                               rel=1e-3)
    want = 6 * (attn + mlp + head) + 12 * 1 * 32 * 128 * 4096
    assert flops.flops_per_token(cfg, 4096) == want
    assert want == pytest.approx(2.315e9, rel=1e-3)


def test_granite_hand_count():
    cfg = load("../../configs/granite-3.0-3b-a800m.json")
    # per layer: q, o 1536x1536; k, v 1536x512; router 1536x40; top-8 of
    # the 40 experts, each 3 x 1536x512; the tied head 1536x49155 counts
    attn = 2 * 1536 * 1536 + 2 * 1536 * 512
    moe = 1536 * 40 + 8 * 3 * 1536 * 512
    head = 1536 * 49155
    assert flops.matmul_params_per_token(cfg) == 4 * (attn + moe) + head
    want = 6 * (4 * (attn + moe) + head) + 12 * 4 * 24 * 64 * 1024
    assert flops.flops_per_token(cfg, 1024) == want
    assert want == pytest.approx(1.134e9, rel=1e-3)


def test_count_ignores_capacity_and_experts_not_chosen():
    cfg = dict(load("../../configs/granite-3.0-3b-a800m.json"))
    base = flops.flops_per_token(cfg, 1024)
    cfg["routing"] = dict(cfg["routing"], capacity_factor=4.0)
    assert flops.flops_per_token(cfg, 1024) == base
    cfg["num_local_experts"] = 80     # only the router grows
    assert flops.flops_per_token(cfg, 1024) - base == 6 * 4 * 1536 * 40
