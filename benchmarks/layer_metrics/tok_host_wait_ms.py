"""Placement: what a chip waits for the host in a step of the traced stretch (feed after a fence, dispatch): the stretch less the chip-busy time in it, both from the trace, in the cells that report ``tok_s_chip``."""


def read(ctx):
    return ctx.host_wait_ms()
