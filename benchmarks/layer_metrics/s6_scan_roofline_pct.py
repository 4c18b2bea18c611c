"""Selective-scan layer: least time the chip could take for the scans the step asks for (``flops_s6.scan_pass_cost`` times the passes, in the job's ``kernel_costs["s6_scan"]``: the larger of its elementwise operations over the chip's peak and its bytes over the HBM's) over ``s6_scan_ms``."""

from benchmarks import flops
from benchmarks.layer_metrics import s6_scan_ms


def read(ctx):
    cost = ctx.job.kernel_costs.get("s6_scan")
    ms = s6_scan_ms.read(ctx)
    if not cost or not ms:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx.peak)[0] / (ms * 1e-3)
