"""Expert layer: device time per step of the operations under ``moe/experts`` (the gate-times-up elementwise pass, the casts of the experts' matrices) and of the grouped-matmul kernels, all passes."""

from benchmarks.layer_metrics.moe_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("experts",))
