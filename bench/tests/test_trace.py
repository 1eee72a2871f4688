"""The trace reduction on a trace built with the chip's plane and line
layout: device planes `/device:TPU:<n>` with `XLA Ops` and `XLA Modules`
lines, a host plane with the harness's spans and the Python thread."""
from types import SimpleNamespace as RunRecord

import pytest

from bench import trace
from bench.harness import PROGRAM_NAME
from bench.metrics import cycle_gap_ms, device_idle_share, mfu

MS = 1_000_000


def ev(plane, line, name, start_ms, dur_ms):
    return trace.Event(plane, line, name, int(start_ms * MS),
                       int(dur_ms * MS))


def two_cycle_trace():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    e = [ev(host, "python", "bench.traced_window", 0, 100),
         ev(host, "python", "dispatch_planned_cycle", 0, 50),
         ev(host, "python", "bench.pool_fetch", 40, 5),
         ev(host, "python", "dispatch_planned_cycle", 50, 50)]
    for plane in (d0, d1):
        e += [ev(plane, "XLA Modules", PROGRAM_NAME + "(1)", 10, 30),
              ev(plane, "XLA Modules", PROGRAM_NAME + "(1)", 60, 30),
              # cycle 1: fusion.1 10-25, fusion.7 20-35 beside it
              ev(plane, "XLA Ops", "fusion.1", 10, 15),
              ev(plane, "XLA Ops", "fusion.7", 20, 15),
              ev(plane, "XLA Ops", "fusion.2", 35, 5),
              # cycle 2: fusion.1 60-80, fusion.3 80-90
              ev(plane, "XLA Ops", "fusion.1", 60, 20),
              ev(plane, "XLA Ops", "fusion.3", 80, 10)]
    return e


def test_union_gaps_and_busy():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.gaps([(2, 3), (5, 7)], (0, 10)) == [(0, 2), (3, 5),
                                                    (7, 10)]
    e = two_cycle_trace()
    w = trace.span(e, "bench.traced_window")
    assert w == (0, 100 * MS)
    # busy 10-40 and 60-90 of the 100 ms window
    assert trace.busy_ns(e, "/device:TPU:0", w) == 60 * MS
    assert trace.device_planes(e) == ["/device:TPU:0", "/device:TPU:1"]


def run_record(events, traced_steps=8):
    # two chips, two traced cycles of B=4; 1e6 model FLOPs per token
    return RunRecord(events=events, window=(0, 100 * MS),
                     traced_steps=traced_steps, traffic={"b_max": 4},
                     program_name=PROGRAM_NAME, chips=2,
                     flops_per_token=1e6, traced_tokens=1200,
                     peak={"bf16_flops_per_s": 1e11})


def test_metric_readers():
    run = run_record(two_cycle_trace())
    assert device_idle_share.read(run) == pytest.approx(40.0)
    assert cycle_gap_ms.read(run) == pytest.approx(20.0)   # 40 -> 60
    # 1.2e9 FLOPs over 2 chips, 60 ms of cycle programs on each chip:
    # 1e10 FLOP/s against a peak of 1e11. The 40 ms of idle do not count.
    assert mfu.read(run) == pytest.approx(10.0)


def test_mfu_needs_one_program_run_per_traced_cycle():
    # three traced cycles, two program runs in the trace
    assert mfu.read(run_record(two_cycle_trace(), traced_steps=12)) is None


def test_program_runs_cut_by_the_window_count_whole():
    e = two_cycle_trace()
    # the host's window ends 5 ms inside the second run: it still counts,
    # whole; one that starts past the middle of its run does not
    assert trace.program_runs(e, "/device:TPU:0", (0, 85 * MS),
                              PROGRAM_NAME) == [(10 * MS, 40 * MS),
                                                (60 * MS, 90 * MS)]
    assert trace.program_runs(e, "/device:TPU:0", (0, 70 * MS),
                              PROGRAM_NAME) == [(10 * MS, 40 * MS)]


def test_readers_return_nothing_without_the_device():
    host_only = [e for e in two_cycle_trace() if e.plane.startswith("/host")]
    run = run_record(host_only)
    for reader in (device_idle_share, cycle_gap_ms, mfu):
        assert reader.read(run) is None


def test_breakdown_ops_and_labelled_gaps():
    e = two_cycle_trace()
    b = trace.breakdown(e, (0, 100 * MS))
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.035)      # per chip
    assert b["device_ops"][0][0] == "fusion.1"
    # device 0 idle: 0-10, 40-60, 90-100; the longest is labelled by the
    # innermost host event over its middle (50 ms: the second dispatch)
    assert b["idle_gaps"][0] == ["dispatch_planned_cycle",
                                 pytest.approx(0.020)]
    assert len(b["idle_gaps"]) == 3
