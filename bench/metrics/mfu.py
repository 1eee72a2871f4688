"""mfu (%): the whole step's share of the chip's bf16 peak. Model FLOPs of
the traced cycles (`bench/flops.py`, from the configuration file) over the
device time of the cycle programs that ran them (their runs on the trace's
`XLA Modules` line, averaged over the cell's chips), over the peak
(`bench/peaks.json`). The host's gaps between programs are left out:
`cycle_gap_ms` and `device_idle_share` read those. Moves
tokens_per_s_per_chip."""
from bench import trace


def read(run):
    planes = trace.device_planes(run.events)
    cycles = run.traced_steps // run.traffic["b_max"]
    runs = [trace.program_runs(run.events, p, run.window, run.program_name)
            for p in planes]
    # every traced cycle is one program run on each chip; a trace that
    # holds another count cannot be attributed
    if not planes or cycles < 1 or any(len(r) != cycles for r in runs):
        return None
    device_s = sum(trace.total(r) for r in runs) / len(runs) / 1e9
    flops_per_chip = run.flops_per_token * run.traced_tokens / run.chips
    return 100.0 * flops_per_chip / device_s / run.peak["bf16_flops_per_s"]
