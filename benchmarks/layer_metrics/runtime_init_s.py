"""Runtime start-up: seconds from the start of the process to the return of ``hvd.init()``: imports, the chips' start-up (``run.py`` asks for the devices before ``hvd.init()``, so init's own ``backend`` phase is near 0 here), the mesh."""

from benchmarks import program_counters


def read(ctx):
    return program_counters.value("hvdtpu_spmd_init_done_process_seconds")
