"""Gated Memory Unit: device time per step of every operation under a ``gmu`` scope (``models/decoder/mixers/gmu.py``: the mixer's norm, the gate's projection, the product with the published scan output, the output projection), all passes."""

from benchmarks.layer_metrics.s6_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, ("gmu",))
