"""CCA attention layer: device time per step of the operations under ``attn/cca_mix`` (both convolutions, the depthwise one's kernels ``hvd_conv_fwd`` / ``hvd_conv_bwd`` included, the q/k means, the value's shift, the L2 norms with the temperature, the rotary embedding), all passes."""

from benchmarks.layer_metrics.cca_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("cca_mix",))
