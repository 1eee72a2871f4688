"""idle_wait_ms (ms): device idle time per cycle while the host still
waits for the cycle's metrics (`repro.wait`: `jax.block_until_ready`,
`core/executor.py` `dispatch_planned_cycle`), after the program's last
op: the time the host takes to see that the device is done. Mean over
the traced dispatch-to-dispatch cycles and the cell's chips
(`bench/phases.py`). Moves tokens_per_s_per_chip."""
from bench import phases


def read(run):
    return phases.idle_ms_under(run, {"repro.wait"})
