"""Model code: seconds a step keeps a chip busy (union of the device's operations over the traced window, averaged over chips, per step), in the cells that report ``tok_s_chip``."""


def read(ctx):
    return ctx.device_step_ms()
