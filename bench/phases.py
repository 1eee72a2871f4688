"""Device idle time under the program's host-loop spans.

The program records each phase of its host loop as a profiler span named
`repro.<phase>` (`src/repro/core/executor.py`, `LOOP_PHASES`): stage,
dispatch, wait, readback, control, checkpoint_save. A cycle here runs
from the start of one `repro.dispatch` span to the start of the next,
inside the traced window, so the window's edges are left out: the
profiler starts and stops inside a `data_fn` call, in the middle of a
`repro.stage` span it cannot record. A device's idle time is the gaps in
the union of its `XLA Ops` intervals. A trace without such spans (a
program that records none) gives no reading."""
from __future__ import annotations

from typing import List

from bench import trace

DISPATCH = "repro.dispatch"


def span_name(name: str) -> str:
    """The span's name without the args the profiler may encode after a
    `#`."""
    return name.split("#", 1)[0]


def host_spans(events, names) -> List[trace.Interval]:
    """The merged intervals of the host spans named in `names`."""
    return trace.union((e.start_ns, e.end_ns) for e in events
                       if e.plane.startswith("/host")
                       and span_name(e.name) in names)


def cycles(events, window: trace.Interval) -> List[trace.Interval]:
    """(start, end) of each dispatch-to-dispatch cycle inside `window`."""
    starts = sorted(e.start_ns for e in events
                    if e.plane.startswith("/host")
                    and span_name(e.name) == DISPATCH
                    and window[0] <= e.start_ns < window[1])
    return list(zip(starts, starts[1:]))


def idle_ms_under(run, names) -> float | None:
    """Mean, over the window's cycles and the cell's chips, of the device
    idle ms that fall under the host spans `names`."""
    planes = trace.device_planes(run.events)
    cyc = cycles(run.events, run.window)
    if not planes or not cyc:
        return None
    under = host_spans(run.events, names)
    idle_ns = 0
    for plane in planes:
        for c in cyc:
            busy = trace.union((e.start_ns, e.end_ns)
                               for e in trace.ops(run.events, plane, c))
            idle_ns += sum(trace.total(trace.clip(under, g))
                           for g in trace.gaps(busy, c))
    return idle_ns / len(cyc) / len(planes) / 1e6
