"""Kimi delta attention layer: least time the chip could take for the scans the step asks for (``flops_kda.scan_pass_cost`` times the passes, in the job's ``kernel_costs["kda_scan"]``: the larger of operations over the MXU's peak and bytes over the HBM's) over ``kda_scan_ms``."""

from benchmarks import flops
from benchmarks.layer_metrics import kda_scan_ms


def read(ctx):
    cost = ctx.job.kernel_costs.get("kda_scan")
    ms = kda_scan_ms.read(ctx)
    if not cost or not ms:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx.peak)[0] / (ms * 1e-3)
