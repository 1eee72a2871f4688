"""cycle_gap_ms (ms): mean device idle time between the end of one
macro-cycle program and the start of the next (`core/executor.py`
`dispatch_planned_cycle` / `run_compiled_training`), over the traced
cycles and the cell's chips. Moves tokens_per_s_per_chip."""
from bench import trace


def read(run):
    gaps = [g for plane in trace.device_planes(run.events)
            for g in trace.program_gaps(run.events, plane, run.window,
                                        run.program_name)]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
