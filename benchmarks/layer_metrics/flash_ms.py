"""Flash kernels: summed device time per step of the Mosaic kernels the job names under ``kernel_costs["flash"]``."""


def read(ctx):
    return ctx.kernel_ms_per_step("flash")
