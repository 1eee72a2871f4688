"""attn_kernel_roofline (%): the fused attention kernel's share of its
roofline: the least time the chip needs for the attention's work, the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(`bench/attn_work.py`: a lower bound from the configuration and traffic),
over the kernel's measured device time (`attn_kernel_ms_per_step`'s
operations). Nothing on a trace without them. Moves
tokens_per_s_per_chip."""
from bench import attn_ops, attn_work


def read(run):
    ns = attn_ops.kernel_ns_per_chip(run)
    if not ns:
        return None
    least_s = max(
        attn_work.flops_per_step(run.cfg, run.traffic) / run.chips
        / run.peak["bf16_flops_per_s"],
        attn_work.bytes_per_step(run.cfg, run.traffic) / run.chips
        / run.peak["hbm_bytes_per_s"]) * run.traced_steps
    return 100.0 * least_s / (ns / 1e9)
