"""Linear-attention layer: device time per step of the operations under ``gdn/in_proj`` and ``gdn/out_proj`` (the mixer's three matmuls and their weight gradients, the optimizer update XLA fuses into those included), all passes."""

from benchmarks.layer_metrics.gdn_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("in_proj", "out_proj"), kernels=False)
