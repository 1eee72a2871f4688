"""moe_gmm_roofline (%): the grouped matrix products' share of their
roofline: the least time the chip needs for their work, the larger of
their FLOPs over the bf16 peak and their bytes over the HBM peak
(`bench/gmm_work.py`, from the configuration and traffic), over their
measured device time (`moe_gmm_ms_per_step`'s operations). Nothing on a
trace without them. Moves tokens_per_s_per_chip."""
from bench import gmm_ops, gmm_work


def read(run):
    ns = gmm_ops.kernel_ns_per_chip(run)
    if not ns:
        return None
    steps = run.traced_steps
    least_s = max(
        gmm_work.flops_per_step(run.cfg, run.traffic) / run.chips
        / run.peak["bf16_flops_per_s"],
        gmm_work.bytes_per_step(run.cfg, run.traffic) / run.chips
        / run.peak["hbm_bytes_per_s"]) * steps
    return 100.0 * least_s / (ns / 1e9)
