"""Compile cache: host seconds of the step's first call (trace, lower, compile or cache load, step 0)."""


def read(ctx):
    return ctx.first_step_s
