"""Flash kernels: device time per step of the dK/dV kernel (``ops/flash_attention.py::KERNEL_DKDV``)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "hvd_flash_dkdv")
