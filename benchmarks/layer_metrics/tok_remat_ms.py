"""Model code: device time per step of the operations under ``rematted_computation`` (the forward pass run again inside the backward pass by ``jax.checkpoint``) on chip 0."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.class_ms(ctx, "recomputation")
