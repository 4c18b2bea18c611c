"""Placement: median host time of one ``hvd.shard_batch`` call, in the cells that report ``img_s_chip``."""


def read(ctx):
    return ctx.span_median_ms("place")
