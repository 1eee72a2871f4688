"""The grouped matmul kernel's operations in a chip trace. The TPU
compiler lowers `jax.lax.ragged_dot` to Mosaic kernels whose HLO names
start with `ragged-dot` (`ragged-dot-none.N`, the products, and
`ragged-dot-metadata.N`, the group offsets they read)."""
from __future__ import annotations

from bench import trace

KERNEL_PREFIX = "ragged-dot"


def kernel_ns_per_chip(run):
    """Device ns of the kernel's leaf operations inside the traced cycle
    programs, averaged over chips; None where the trace has none."""
    planes = trace.device_planes(run.events)
    per_chip = []
    for plane in planes:
        runs = trace.program_runs(run.events, plane, run.window,
                                  run.program_name)
        ops = [e for a, b in runs
               for e in trace.leaf_ops(trace.ops(run.events, plane, (a, b)))
               if trace.op_name(e.name).startswith(KERNEL_PREFIX)]
        per_chip.append(sum(e.dur_ns for e in ops))
    if not planes or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip)
