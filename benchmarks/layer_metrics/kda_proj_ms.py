"""Kimi delta attention layer: device time per step of the operations under ``kda/kda_proj`` (the projections to ``q | k | v``, to the gate's channels, to ``beta`` and the output gate, the output projection, and their weight gradients, the optimizer update XLA fuses into those included), all passes."""

from benchmarks.layer_metrics.kda_ms import OUTER, scope_ms


def read(ctx):
    return scope_ms(ctx, OUTER, inner=("kda_proj",))
