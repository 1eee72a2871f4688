"""idle_readback_ms (ms): device idle time per cycle while the host reads
the cycle's metrics back (`repro.readback`: the copy of each metric array
and its conversion to floats, `core/executor.py`
`dispatch_planned_cycle`). Mean over the traced dispatch-to-dispatch
cycles and the cell's chips (`bench/phases.py`). Moves
tokens_per_s_per_chip."""
from bench import phases


def read(run):
    return phases.idle_ms_under(run, {"repro.readback"})
