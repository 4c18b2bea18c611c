"""Optimizer update: device time per step of the operations under ``hvd_optimizer`` on chip 0, with the fusions XLA roots in the job's own ``optax.apply_updates`` (``scope_reduce.class_of``), in the cells that report ``img_s_chip``."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.class_ms(ctx, "optimizer")
