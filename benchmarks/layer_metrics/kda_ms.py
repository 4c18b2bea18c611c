"""Kimi delta attention layer: device time per step, on chip 0, of every operation under a ``kda`` scope (``models/decoder/mixers/kda.py``: the mixer's norm, ``kda_proj``, ``kda_conv`` with its ``hvd_conv_*`` kernels, ``kda_scan`` with the ``hvd_kda_*`` kernels, ``kda_gate``; forward, recomputed and backward)."""

from benchmarks.layer_metrics.s6_ms import scope_ms

OUTER = ("kda",)


def read(ctx):
    return scope_ms(ctx, OUTER)
