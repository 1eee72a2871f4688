"""device_idle_share (%): 1 - (union of the operations' intervals) / the
traced window, averaged over the cell's chips. Moves
tokens_per_s_per_chip."""
from bench import trace


def read(run):
    planes = trace.device_planes(run.events)
    width = run.window[1] - run.window[0]
    if not planes or width <= 0:
        return None
    busy = [trace.busy_ns(run.events, p, run.window) for p in planes]
    return 100.0 * (1.0 - sum(busy) / len(busy) / width)
