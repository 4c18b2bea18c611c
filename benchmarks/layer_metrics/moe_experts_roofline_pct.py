"""Expert layer: least time the chip could take for the grouped matmuls the step asks for (``flops_moe.grouped_matmul_pass_cost`` times the passes, in the job's ``kernel_costs``) over ``moe_experts_ms``."""

from benchmarks import flops
from benchmarks.layer_metrics import moe_experts_ms


def read(ctx):
    cost = ctx.job.kernel_costs.get("grouped_matmul")
    ms = moe_experts_ms.read(ctx)
    if not cost or not ms:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx.peak)[0] / (ms * 1e-3)
