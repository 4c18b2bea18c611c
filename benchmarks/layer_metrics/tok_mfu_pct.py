"""Model code: model FLOP/s utilization from ``flops.py`` and the untraced throughput, in the cells that report ``tok_s_chip``."""


def read(ctx):
    return ctx.mfu_pct()
