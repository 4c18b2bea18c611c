"""Kimi delta attention layer: device time per step of the ``hvd_kda_*`` kernels, by name, and of what else lies under ``kda/kda_scan`` (the gate's sigmoid and bound, the writing strength, the chunks' last decays), each operation once, all passes."""

from benchmarks.layer_metrics.kda_ms import OUTER, scope_ms

KERNEL = r"^hvd_kda_"


def read(ctx):
    return scope_ms(ctx, OUTER, inner=("kda_scan",), kernel=KERNEL)
