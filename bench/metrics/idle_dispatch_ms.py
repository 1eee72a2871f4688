"""idle_dispatch_ms (ms): device idle time per cycle while the host
launches the cycle program (`repro.dispatch`: `MacroCycleExecutor.run_cycle`
up to its return, `core/executor.py` `dispatch_planned_cycle`). Mean over
the traced dispatch-to-dispatch cycles and the cell's chips
(`bench/phases.py`). Moves tokens_per_s_per_chip."""
from bench import phases


def read(run):
    return phases.idle_ms_under(run, {"repro.dispatch"})
