"""Gradient exchange: summed device time per step of the all-reduce operations on chip 0."""

ALL_REDUCE = r"^all-reduce"


def read(ctx):
    return ctx.op_ms_per_step(ALL_REDUCE)
