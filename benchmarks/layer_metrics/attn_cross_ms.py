"""Differential attention: device time per step of every operation under an ``attn_cross`` scope (a layer that reads an earlier layer's keys and values: its norm, the query projection, its two flash calls' kernels, the combination under ``diff``, the output projection), all passes."""

from benchmarks.layer_metrics.s6_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, ("attn_cross",))
