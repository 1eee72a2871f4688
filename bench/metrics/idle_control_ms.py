"""idle_control_ms (ms): device idle time per cycle while the host plans
and observes cycles and runs the checkpoint callback (`repro.control`:
`plan_cycle`, `observe`; `repro.checkpoint_save`; `core/executor.py`
`run_compiled_training`). Mean over the traced dispatch-to-dispatch
cycles and the cell's chips (`bench/phases.py`). Moves
tokens_per_s_per_chip."""
from bench import phases


def read(run):
    return phases.idle_ms_under(run, {"repro.control",
                                      "repro.checkpoint_save"})
