"""Device: what the fullest chip held at most (``peak_bytes_in_use`` + ``peak_bytes_reserved``), in the cells that report ``img_s_chip``."""


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 30
