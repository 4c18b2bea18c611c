"""Selective-scan layer: device time per step of the ``hvd_s6_*`` kernels, by name, and of what else lies under ``s6/scan`` (the step size's projection and soft-plus, ``-exp(A_log)``, the transposes of ``B`` and ``C`` into the kernels' groups, the sums of the backward kernel's parts), each operation once, all passes."""

from benchmarks.layer_metrics.s6_ms import scope_ms

KERNEL = r"^hvd_s6_"


def read(ctx):
    return scope_ms(ctx, ("s6",), inner=("scan",), kernel=KERNEL)
