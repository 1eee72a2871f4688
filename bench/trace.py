"""Reduction of a profiler trace to numbers.

A trace is read into flat `Event`s (plane, line, name, start, duration in
ns), so that every reduction below is plain arithmetic on intervals and a
test can build a trace with the same plane and line layout. Device planes
are named `/device:<KIND>:<n>`; their `XLA Ops` line holds the operations
that ran, their `XLA Modules` line the programs. Host planes (`/host:...`)
hold the Python thread's calls and the harness's own
`jax.profiler.TraceAnnotation` spans (names starting `bench.`)."""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def load(log_dir: str) -> List[Event]:
    """Every event of the one `.xplane.pb` under `log_dir`."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {log_dir}, found "
                         f"{len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    return [Event(pl.name, ln.name, ev.name, int(ev.start_ns),
                  int(ev.duration_ns))
            for pl in data.planes for ln in pl.lines for ev in ln.events]


def device_planes(events: List[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith("/device:") and e.line == OPS_LINE})


def span(events: List[Event], name: str) -> Optional[Interval]:
    """(start, end) of the host span `name` (the first one)."""
    for e in events:
        if e.plane.startswith("/host") and e.name == name:
            return e.start_ns, e.end_ns
    return None


def clip(intervals, window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of `window` outside the (merged) `busy` ones."""
    out, t = [], window[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def ops(events, plane, window: Interval) -> List[Event]:
    lo, hi = window
    return [e for e in events if e.plane == plane and e.line == OPS_LINE
            and e.end_ns > lo and e.start_ns < hi]


def busy_ns(events, plane, window: Interval) -> int:
    return total(union(clip([(e.start_ns, e.end_ns)
                             for e in ops(events, plane, window)], window)))


def program_runs(events, plane, window: Interval,
                 name_part: str) -> List[Interval]:
    """(start, end) of each run of the programs whose module name contains
    `name_part` and whose middle lies inside `window`, whole and in order:
    a run that the window's edge cuts by the offset between the host's
    and the device's clocks still counts once."""
    return sorted((e.start_ns, e.end_ns) for e in events
                  if e.plane == plane and e.line == MODULES_LINE
                  and name_part in e.name
                  and window[0] <= e.start_ns + e.dur_ns // 2 < window[1])


def program_gaps(events, plane, window: Interval, name_part: str):
    """Idle ns between consecutive runs of those programs (the end of
    one to the start of the next)."""
    runs = program_runs(events, plane, window, name_part)
    return [b[0] - a[1] for a, b in zip(runs, runs[1:])]


def leaf_ops(evs: List[Event]) -> List[Event]:
    """The operations that contain no other: a `while` or `call` event of
    the ops line spans the operations of its body, which follow it."""
    evs = sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or not (nxt.start_ns < e.end_ns
                                   and nxt.end_ns <= e.end_ns)]


def op_name(name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...), ...` (an HLO instruction, as
    the TPU trace names operations) -> `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def host_label(events, t: int) -> str:
    """What the host was doing at `t`: the innermost host event that
    covers it (a harness span or a call of the Python thread)."""
    best = None
    for e in events:
        if (e.plane.startswith("/host") and e.start_ns <= t < e.end_ns
                and (best is None or e.dur_ns < best.dur_ns)):
            best = e
    return best.name if best is not None else "(no host event)"


def breakdown(events, window: Interval, top: int = 10) -> dict:
    """The device operations that took most time (seconds per chip) and
    the longest idle gaps of the first device, each labelled by what the
    host was doing in its middle."""
    planes = device_planes(events)
    per_op = defaultdict(int)
    for plane in planes:
        for e in leaf_ops(ops(events, plane, window)):
            for a, b in clip([(e.start_ns, e.end_ns)], window):
                per_op[op_name(e.name)] += b - a
    n = max(1, len(planes))
    device_ops = sorted(([k, v / n / 1e9] for k, v in per_op.items()),
                        key=lambda kv: -kv[1])[:top]
    idle = []
    if planes:
        busy = union(clip([(e.start_ns, e.end_ns)
                           for e in ops(events, planes[0], window)], window))
        longest = sorted(gaps(busy, window), key=lambda g: g[0] - g[1])[:top]
        idle = [[host_label(events, (a + b) // 2), (b - a) / 1e9]
                for a, b in longest]
    return {"device_ops": device_ops, "idle_gaps": idle}
