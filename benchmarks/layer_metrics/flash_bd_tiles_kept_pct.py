"""Flash kernels: tiles the block-diffusion grids compute over the tiles of the 2 L x 2 L rectangle of the step's rows (the noised and the clean copy of a sequence), from the program's own count at trace time (``hvdtpu_spmd_flash_tiles_total`` with ``mask="block_diffusion"``: ``kept`` over ``kept + skipped_block_diffusion``, every kernel); 80 of 256 at 8192 data tokens and 1024-wide tiles, where the causal triangle of 16,384 rows keeps 136. None where the program counts no such mask."""

from benchmarks import program_counters

FAMILY = "hvdtpu_spmd_flash_tiles_total"
PAD = 128       # the kernels pad a sequence to this many rows


def read(ctx):
    seq = str(-(-2 * ctx.job.seq // PAD) * PAD)
    kept, skipped = (program_counters.value(
        FAMILY, mask="block_diffusion", tiles=tiles, seq=seq)
        for tiles in ("kept", "skipped_block_diffusion"))
    if not kept:
        return None
    return 100.0 * kept / (kept + (skipped or 0))
