"""The harness's check on a tiny mixture of experts whose capacity never
binds, so that the program takes its dropless sorted path: a sound run is
`correct`, one expert's rows left out of the combine is not, and neither
are the float8 control and the planted faults. Then the two readers of the
grouped matmul kernel on a trace built with the chip's layout."""
import jax
import pytest

from bench import calibrate, checks, gmm_work
from bench.metrics import moe_gmm_ms_per_step, moe_gmm_roofline
from bench.tests.helpers import tiny_cell
from bench.tests.test_correct import SEED, _run
from bench.tests.test_trace import ev, run_record, two_cycle_trace

CELL = ("tiny-moe-dropless.json", "tiny-moe-dropless-limits.json")


def test_tiny_cell_takes_the_dropless_path():
    from bench import model
    assert model.arch_config(tiny_cell(*CELL).config).moe.dropless


def test_sound_dropless_run_is_correct():
    res, values = _run(tiny_cell(*CELL))
    assert res["correct"], values


def _one_expert_left_out(monkeypatch):
    """The combine weights every row of expert 0 by zero: in sorted order
    they are the first sizes[0] rows."""
    import repro.models.moe as moe
    sorted_experts = moe._sorted_experts

    def broken(xt, we1, we3, we2, gate_vals, order, inv, sizes):
        left_out = (inv < sizes[0]).reshape(gate_vals.shape)
        return sorted_experts(xt, we1, we3, we2,
                              gate_vals * (1 - left_out), order, inv, sizes)
    monkeypatch.setattr(moe, "_sorted_experts", broken)


def test_expert_left_out_of_the_combine_is_not_correct(monkeypatch):
    _one_expert_left_out(monkeypatch)
    res, values = _run(tiny_cell(*CELL))
    assert not res["correct"], values


def test_float8_control_runs_and_fails():
    cell = tiny_cell(*CELL)
    rows, _ = calibrate.calibrate(cell, jax.devices()[:1], 1, 1, SEED,
                                  log=lambda s: None)
    row = rows[0]
    assert checks.judge(row["program"], cell.limits), row["program"]
    assert "crashed" not in row["control"], row["control"]
    assert not checks.judge(row["control"], cell.limits), row["control"]
    for kind in calibrate.FAULTS:
        assert not checks.judge(row[kind], cell.limits), (kind, row)


GRANITE = {"num_hidden_layers": 4, "hidden_size": 1536,
           "intermediate_size": 512, "num_local_experts": 40,
           "num_experts_per_tok": 8}
TRAFFIC = {"replicas": 2, "sequences_per_replica": 1, "seq_len": 2048,
           "b_max": 4}


def test_gmm_work_counts():
    rows = 2048 * 8
    assert gmm_work.flops_per_step(GRANITE, TRAFFIC) == (
        9 * 2 * rows * 1536 * 512 * 4 * 2)
    assert gmm_work.bytes_per_step(GRANITE, TRAFFIC) == (
        9 * 2 * (rows * 1536 + rows * 512 + 40 * 1536 * 512) * 4 * 2)


def _gmm_trace():
    """The two-cycle trace with grouped matmul ops in both cycles of both
    chips: 2 + 3 ms in cycle 1 and 4 ms in cycle 2, and one 50 ms op
    outside the cycle programs, which does not count."""
    e = two_cycle_trace()
    for plane in ("/device:TPU:0", "/device:TPU:1"):
        e += [ev(plane, "XLA Ops", "%ragged-dot-none.2 = bf16[16384,512] "
                 "custom-call(%a, %b)", 11, 2),
              ev(plane, "XLA Ops", "ragged-dot-metadata.1", 14, 3),
              ev(plane, "XLA Ops", "ragged-dot-none.5", 61, 4),
              ev(plane, "XLA Ops", "ragged-dot-none.9", 91, 5)]
    return e


def test_gmm_readers():
    run = run_record(_gmm_trace())
    run.cfg, run.traffic = GRANITE, dict(TRAFFIC, b_max=4)
    run.peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 9 ms of kernel ops per chip over 8 traced steps
    assert moe_gmm_ms_per_step.read(run) == pytest.approx(9 / 8)
    least_s = max(gmm_work.flops_per_step(GRANITE, TRAFFIC) / 2 / 197e12,
                  gmm_work.bytes_per_step(GRANITE, TRAFFIC) / 2 / 819e9)
    assert moe_gmm_roofline.read(run) == pytest.approx(
        100 * least_s * 8 / 9e-3)


def test_gmm_readers_return_nothing_without_the_kernel():
    run = run_record(two_cycle_trace())
    run.cfg, run.traffic = GRANITE, TRAFFIC
    run.peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert moe_gmm_ms_per_step.read(run) is None
    assert moe_gmm_roofline.read(run) is None
