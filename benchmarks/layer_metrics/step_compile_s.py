"""Compile cache: seconds the backend compiled the job's step function or loaded it from the persistent cache (``hvd.metrics()``)."""

from benchmarks import program_counters


def read(ctx):
    return program_counters.step_seconds(ctx, "backend_compile")
