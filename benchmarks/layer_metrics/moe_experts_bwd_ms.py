"""Expert layer: device time per step, on chip 0, of the grouped-matmul kernels of the backward pass (the rows' cotangents and the matrices' gradients), as the compiled step's report places them (``pass`` ``backward``)."""

from benchmarks.layer_metrics.moe_experts_fwd_ms import pass_ms


def read(ctx):
    return pass_ms(ctx, "backward")
