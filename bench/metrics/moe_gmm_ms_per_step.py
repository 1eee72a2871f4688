"""moe_gmm_ms_per_step (ms): device time per traced step of the dropless
expert layer's grouped matrix products: the operations of the `XLA Ops`
line whose HLO name starts with `ragged-dot` (the TPU compiler's grouped
matmul kernel and its group metadata, `bench/gmm_ops.py`), inside the
traced cycle programs (`jit_program` runs), averaged over the cell's
chips. Nothing on a trace without them. Moves tokens_per_s_per_chip."""
from bench import gmm_ops


def read(run):
    ns = gmm_ops.kernel_ns_per_chip(run)
    return None if ns is None else ns / run.traced_steps / 1e6
