"""Linear-attention layer: device time per step of the operations under ``gdn/scan`` (the L2 norms of q and k, the decays and writing strengths, the chunked gated delta rule: the chunk's products, its unit lower triangular inverse, the recurrence over chunks) and of any ``hvd_gdn_*`` kernel, all passes."""

from benchmarks.layer_metrics.gdn_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("scan",))
