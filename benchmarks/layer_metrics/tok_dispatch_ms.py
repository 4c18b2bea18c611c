"""Compiled step: median host time of one ``step(...)`` call (the dispatch, not the step), in the cells that report ``tok_s_chip``."""


def read(ctx):
    return ctx.span_median_ms("dispatch")
