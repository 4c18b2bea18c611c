"""Expert layer: device time per step of the operations under ``moe/shared`` (``models/gpt.py::_shared_expert``: the expert every token goes through, on the stream, beside the routed ones), all passes: forward, recomputed and backward. None where the trace holds no such scope (a program without a shared expert)."""

from benchmarks.layer_metrics.moe_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("shared",), kernels=False)
