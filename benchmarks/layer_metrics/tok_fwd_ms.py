"""Model code: device time per step of the operations under ``jvp(`` (forward pass) on chip 0, in the cells that report ``tok_s_chip`` (``scope_reduce.py`` has the classes)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.class_ms(ctx, "forward")
