"""idle_stage_ms (ms): device idle time per cycle while the host stages
the next cycle (`repro.stage`: the `data_fn` calls, stacking the batches
on the device, the learning-rate upload, `core/executor.py`
`dispatch_planned_cycle`). Mean over the traced dispatch-to-dispatch
cycles and the cell's chips (`bench/phases.py`). Moves
tokens_per_s_per_chip."""
from bench import phases


def read(run):
    return phases.idle_ms_under(run, {"repro.stage"})
