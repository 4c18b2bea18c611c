"""Model code: device time per step of the operations under ``transpose(jvp(`` (backward pass, recomputation apart) on chip 0, in the cells that report ``tok_s_chip``."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.class_ms(ctx, "backward")
