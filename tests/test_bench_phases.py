"""The benchmark's readers of the program's host-loop spans
(`bench/phases.py`, `bench/metrics/idle_*_ms.py`) on a trace built with
the chip's plane and line layout: device planes `/device:TPU:<n>` with an
`XLA Ops` line, a host plane with the harness's window and the program's
`repro.<phase>` spans."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import phases, spec, trace  # noqa: E402

MS = 1_000_000
HOST, D0, D1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
READERS = ("idle_readback_ms", "idle_stage_ms", "idle_dispatch_ms",
           "idle_control_ms", "idle_wait_ms")


def ev(plane, line, name, start_ms, end_ms):
    return trace.Event(plane, line, name, int(start_ms * MS),
                       int((end_ms - start_ms) * MS))


def host(name, a, b):
    return ev(HOST, "python", name, a, b)


def two_cycle_trace():
    """Three dispatches, so two dispatch-to-dispatch cycles, [10, 60) and
    [60, 110). Device idle (ms) in them:

      cycle 1  10-13  dispatch 2, wait 1
               40-50  readback 5 (chip 1: 3, it runs an op at 40-42),
                      control 2, checkpoint_save 1, stage 2
               52-60  stage 6, under no span 2
      cycle 2  60-62  dispatch 2
               90-102 readback 7, control 2, checkpoint_save 1, stage 2
               103-110 stage 5, under no span 2

    The stage span at 0-8 and the idle 0-10 lie before the first
    dispatch, outside every cycle."""
    e = [host("bench.traced_window", 0, 130),
         host("repro.stage", 0, 8)]
    for d, first in ((10, True), (60, False)):
        e += [host("repro.cycle", d, d + 48),
              host("repro.dispatch" if first else "repro.dispatch#compiles=0#",
                   d, d + (2 if first else 3)),
              host("repro.wait", d + (2 if first else 3), d + 30),
              host("repro.readback", d + 30, d + (35 if first else 37)),
              host("repro.control", d + (35 if first else 37),
                   d + (36 if first else 38)),
              host("repro.checkpoint_save", d + (36 if first else 38),
                   d + (37 if first else 39)),
              host("repro.control", d + (37 if first else 39),
                   d + (38 if first else 40)),
              host("repro.stage", d + (38 if first else 40), d + 48),
              # the Python tracer's calls nest under the spans
              host("_array.py:436___array__", d + 31, d + 32)]
    e += [host("repro.dispatch", 110, 112), host("repro.wait", 112, 125)]
    for plane in (D0, D1):
        e += [ev(plane, "XLA Ops", "fusion.1", 13, 30),
              ev(plane, "XLA Ops", "fusion.2", 30, 40),
              ev(plane, "XLA Ops", "stack", 50, 52),
              ev(plane, "XLA Ops", "fusion.1", 62, 90),
              ev(plane, "XLA Ops", "stack", 102, 103),
              ev(plane, "XLA Ops", "fusion.1", 111, 125)]
    e.append(ev(D1, "XLA Ops", "copy.3", 40, 42))
    return e


class Run:
    def __init__(self, events, window=(0, 130 * MS)):
        self.events, self.window = events, window


def read(name, run):
    return spec.metric_reader(name)(run)


def test_readers_attribute_idle_ms_to_their_spans():
    run = Run(two_cycle_trace())
    # per cycle and chip: readback (5 + 7 + 3 + 7) / 4
    assert read("idle_readback_ms", run) == pytest.approx(5.5)
    # stage (2 + 6 + 2 + 5) per chip / 2 cycles; 0-8 is outside
    assert read("idle_stage_ms", run) == pytest.approx(7.5)
    assert read("idle_dispatch_ms", run) == pytest.approx(2.0)
    # control and checkpoint_save: (2 + 1) per cycle
    assert read("idle_control_ms", run) == pytest.approx(3.0)
    # wait: the 1 ms of cycle 1 before its program's first op
    assert read("idle_wait_ms", run) == pytest.approx(0.5)


def test_idle_under_no_loop_span_is_not_attributed():
    run = Run(two_cycle_trace())
    idle = [trace.total(trace.gaps(trace.union(trace.clip(
        [(e.start_ns, e.end_ns) for e in trace.ops(run.events, p, c)], c)),
        c)) for p in (D0, D1) for c in phases.cycles(run.events, run.window)]
    per_cycle = sum(idle) / len(idle) / MS       # (21 + 21 + 19 + 21) / 4
    assert per_cycle == pytest.approx(20.5)
    attributed = sum(read(n, run) for n in READERS)
    # the rest: 2 ms under no span per cycle
    assert per_cycle - attributed == pytest.approx(2.0)


def test_cycles_run_dispatch_to_dispatch_inside_the_window():
    e = two_cycle_trace()
    assert phases.cycles(e, (0, 130 * MS)) == [(10 * MS, 60 * MS),
                                               (60 * MS, 110 * MS)]
    # a window that ends before the third dispatch leaves one cycle
    assert phases.cycles(e, (0, 100 * MS)) == [(10 * MS, 60 * MS)]
    one = Run(e, window=(0, 100 * MS))
    assert read("idle_readback_ms", one) == pytest.approx((5 + 3) / 2)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_spans_or_device(name):
    e = two_cycle_trace()
    # the parent program: no repro. spans on the host plane
    bare = [x for x in e if not x.name.startswith("repro.")]
    assert read(name, Run(bare)) is None
    # one dispatch: no complete cycle
    one = [x for x in e if not (x.name.startswith("repro.dispatch")
                                and x.start_ns >= 60 * MS)]
    assert read(name, Run(one)) is None
    host_only = [x for x in e if x.plane == HOST]
    assert read(name, Run(host_only)) is None


def test_span_names_drop_encoded_args():
    assert phases.span_name("repro.dispatch#compiles=0#") == "repro.dispatch"
    assert phases.span_name("repro.stage") == "repro.stage"


def test_benchmark_lists_each_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["layer"] == "loop and executor" and m["unit"] == "ms"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert callable(spec.metric_reader(name))
