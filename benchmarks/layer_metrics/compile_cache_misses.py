"""Compile cache: executables this process compiled and wrote to the persistent cache, of any function; 0 on a warm run (``hvd.metrics()``)."""

from benchmarks import program_counters


def read(ctx):
    return program_counters.value("hvdtpu_spmd_compile_cache_misses_total")
