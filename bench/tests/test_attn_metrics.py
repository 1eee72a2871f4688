"""The fused attention kernel's readers on synthetic traces with the
chip's plane and line layout (`test_trace.two_cycle_trace`)."""
import pytest

from bench import attn_work
from bench.metrics import attn_kernel_ms_per_step, attn_kernel_roofline
from bench.tests.helpers import load
from bench.tests.test_trace import ev, run_record, two_cycle_trace

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "hidden_size": 64, "assumed": {}}
TINY_TRAFFIC = {"replicas": 2, "sequences_per_replica": 3, "seq_len": 8,
                "b_max": 4}


def _attn_trace():
    """The two-cycle trace with the kernel's ops in both cycles of both
    chips, 1 + 2 + 3 ms in cycle 1 and 4 ms in cycle 2, and one 50 ms
    kernel op outside the cycle programs, which does not count."""
    e = two_cycle_trace()
    for plane in ("/device:TPU:0", "/device:TPU:1"):
        e += [ev(plane, "XLA Ops", "%flash_attn_fwd.3 = bf16[2,1,32,4096,"
                 "128] custom-call(%q, %k, %v)", 11, 1),
              ev(plane, "XLA Ops", "flash_attn_bwd_dq.1", 13, 2),
              ev(plane, "XLA Ops", "flash_attn_bwd_dkv.2", 16, 3),
              ev(plane, "XLA Ops", "flash_attn_fwd.7", 61, 4),
              ev(plane, "XLA Ops", "flash_attn_fwd.3", 91, 50)]
    return e


def _run(events, cfg, traffic):
    run = run_record(events)
    run.cfg, run.traffic, run.peak = cfg, traffic, PEAK
    return run


def test_work_matches_a_hand_count():
    # 2 layers x 2 replicas x 3 sequences = 12 (layer, sequence) pairs;
    # head_dim 64 / 4 = 16; causal pairs 8 x 9 / 2 = 36
    assert attn_work.flops_per_step(TINY, TINY_TRAFFIC) == (
        12 * 6 * 2 * 4 * 16 * 36)
    # q, o, dO read, o and dq written: 5 x (8 x 4 x 16); k, v read and
    # dk, dv written: 4 x (8 x 2 x 16); 2 bytes each
    assert attn_work.bytes_per_step(TINY, TINY_TRAFFIC) == (
        12 * 2 * (5 * 8 * 4 * 16 + 4 * 8 * 2 * 16))


def test_readers_on_the_kernel_trace():
    run = _run(_attn_trace(), TINY, TINY_TRAFFIC)
    # 10 ms of kernel ops per chip over 8 traced steps
    assert attn_kernel_ms_per_step.read(run) == pytest.approx(10 / 8)
    least_s = max(attn_work.flops_per_step(TINY, TINY_TRAFFIC) / 2 / 197e12,
                  attn_work.bytes_per_step(TINY, TINY_TRAFFIC) / 2 / 819e9)
    share = attn_kernel_roofline.read(run)
    assert share == pytest.approx(100 * least_s * 8 / 10e-3)
    assert 0 < share < 100


def test_mistral_cell_is_bound_by_flops():
    """The Mistral cell's attention: 8.25e11 FLOP per step need 4.19 ms
    at the bf16 peak, its 4.03e8 bytes 0.49 ms at the HBM peak."""
    mistral = load("../../configs/mistral-7b-v0.3.json")
    traffic = load("../../traffic/daso-b4.r2x1x4096.json")
    assert attn_work.flops_per_step(mistral, traffic) == (
        2 * 6 * 2 * 32 * 128 * 4096 * 4097 / 2)
    assert attn_work.bytes_per_step(mistral, traffic) == (
        2 * 2 * 4096 * 128 * (5 * 32 + 4 * 8))
    assert attn_work.flops_per_step(mistral, traffic) / 197e12 > (
        attn_work.bytes_per_step(mistral, traffic) / 819e9)


def test_readers_return_nothing_without_the_kernel():
    for cfg, traffic in (
            ("mistral-7b-v0.3.json", "daso-b4.r2x1x4096.json"),
            ("granite-3.0-3b-a800m.dropless.json",
             "daso-b4.r2x1x2048.json")):
        run = _run(two_cycle_trace(), load(f"../../configs/{cfg}"),
                   load(f"../../traffic/{traffic}"))
        assert attn_kernel_ms_per_step.read(run) is None
        assert attn_kernel_roofline.read(run) is None
