"""Differential attention: device time per step of the operations under ``diff`` inside the three attention scopes (``attn_window``, ``attn``, ``attn_cross``): the difference of the two maps' outputs under the learned scalar, the RMSNorm a pair of heads and their backward: what differential attention adds to the flash kernels' time."""

from benchmarks.layer_metrics.s6_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, ("attn_window", "attn", "attn_cross"),
                    inner=("diff",))
