"""Device: device time per step on chip 0 that no class claims (no ``op_name``, or one with none of the program's or JAX's scopes), in the cells that report ``tok_s_chip``."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.class_ms(ctx, "unscoped")
