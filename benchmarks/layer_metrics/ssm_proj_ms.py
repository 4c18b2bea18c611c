"""State-space layer: device time per step of the operations under ``ssm/in_proj`` and ``ssm/out_proj`` (the mixer's two matmuls and their weight gradients, the optimizer update XLA fuses into those included), all passes."""

from benchmarks.layer_metrics.ssm_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("in_proj", "out_proj"), kernels=False)
