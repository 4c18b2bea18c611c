"""Expert layer: device time per step of the operations under ``moe/latent_down`` and ``moe/latent_up`` (``models/gpt.py::_expert_ff``: the projection of the normed stream to the latent the routed experts run in and the one of their weighted sum back to the stream, with their weight gradients and the optimizer update XLA fuses into those), all passes: forward, recomputed and backward. None where the trace holds no such scope (a program whose experts run at the stream's width)."""

from benchmarks.layer_metrics.moe_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("latent_down", "latent_up"), kernels=False)
