"""Flash kernels: least time the chip could take for the pairs the window layers keep under their band (``flops_window.band_pairs``, one forward and one backward a layer: the job's ``window_flash_cost``) over ``flash_window_ms``."""

from benchmarks import flops
from benchmarks.layer_metrics import flash_window_ms


def read(ctx):
    cost = getattr(ctx.job, "window_flash_cost", None)
    ms = flash_window_ms.read(ctx)
    if not cost or not ms:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx.peak)[0] / (ms * 1e-3)
