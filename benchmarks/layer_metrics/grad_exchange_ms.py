"""Gradient exchange: device time per step of everything under ``hvd_exchange`` (quantize and dequantize kernels included) and of the all-reduce autodiff inserts for replicated parameters (``psum_invariant``), on chip 0."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.class_ms(ctx, "exchange")
