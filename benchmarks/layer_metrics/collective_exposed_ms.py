"""Gradient exchange: the part of the all-reduce time on chip 0 in which no other operation runs there."""

from benchmarks.layer_metrics.collective_ms import ALL_REDUCE


def read(ctx):
    return ctx.op_ms_per_step(ALL_REDUCE, exposed=True)
