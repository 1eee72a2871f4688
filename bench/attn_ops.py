"""The fused attention kernel's operations in a chip trace. The program
names every `pallas_call` of its flash attention (forward, and the dq and
dk/dv backward kernels) with the prefix `flash_attn`, and the TPU
compiler names each custom call after it (`flash_attn_fwd.N`,
`flash_attn_bwd_dq.N`, `flash_attn_bwd_dkv.N`)."""
from __future__ import annotations

from bench import trace

KERNEL_PREFIX = "flash_attn"


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
