"""attn_kernel_ms_per_step (ms): device time per traced step of the fused
attention kernel: the operations of the `XLA Ops` line whose HLO name
starts with `flash_attn` (forward, dq and dk/dv kernels,
`bench/attn_ops.py`), inside the traced cycle programs (`jit_program`
runs), averaged over the cell's chips. Nothing on a trace without them.
Moves tokens_per_s_per_chip."""
from bench import attn_ops


def read(run):
    ns = attn_ops.kernel_ns_per_chip(run)
    return None if ns is None else ns / run.traced_steps / 1e6
