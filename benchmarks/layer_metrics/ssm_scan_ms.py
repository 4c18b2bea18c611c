"""State-space layer: device time per step of the operations under ``ssm/scan`` (the soft-plus of the step sizes, the decays, the chunked scan's four products, the recurrence over chunks, the skip) and of any ``hvd_ssd_*`` kernel, all passes."""

from benchmarks.layer_metrics.ssm_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("scan",))
