"""Expert layer: device time per step of the operations under ``moe/router_early`` (``models/gpt.py::_early_router``: the float32 product of a router that reads the block's input, made before the mixer runs), all passes: forward, recomputed and backward. None where the trace holds no such scope (a program whose routers read what the experts read)."""

from benchmarks.layer_metrics.moe_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("router_early",), kernels=False)
