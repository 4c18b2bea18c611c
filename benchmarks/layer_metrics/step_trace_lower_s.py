"""Compiled step: seconds JAX traced and lowered the job's step function, all calls (``hvd.metrics()``)."""

from benchmarks import program_counters


def read(ctx):
    return program_counters.step_seconds(ctx, "trace", "lower")
