"""Flash kernels: tiles the window layers' grids compute over the tiles the causal triangle's would, at the step's sequence length, from the program's own count at trace time (``hvdtpu_spmd_flash_tiles_total``: ``kept`` over ``kept + skipped_band``, all three kernels); 21 of 36 at 8192 tokens, a window of 2048 and 1024-wide tiles."""

from benchmarks import program_counters

FAMILY = "hvdtpu_spmd_flash_tiles_total"
PAD = 128       # the kernels pad a sequence to this many rows


def read(ctx):
    seq = str(-(-ctx.job.seq // PAD) * PAD)
    kept, outside = (program_counters.value(
        FAMILY, mask="window", tiles=tiles, seq=seq)
        for tiles in ("kept", "skipped_band"))
    if not kept:
        return None
    return 100.0 * kept / (kept + (outside or 0))
