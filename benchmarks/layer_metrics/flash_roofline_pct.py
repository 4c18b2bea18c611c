"""Flash kernels: least time the chip could take for what the step asks of them (``flops.py``) over the time they took."""


def read(ctx):
    share = ctx.kernel_roofline("flash")
    return share[0] if share else None
