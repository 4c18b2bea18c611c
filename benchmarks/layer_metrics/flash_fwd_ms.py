"""Flash kernels: device time per step of the forward kernel (``ops/flash_attention.py::KERNEL_FWD``), recomputed calls included."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "hvd_flash_fwd")
