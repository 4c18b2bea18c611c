"""Compiled step: executables the jitted step holds after the window. Anything but 1 is a compile the caller did not ask for."""


def read(ctx):
    return float(ctx.step_compiles)
