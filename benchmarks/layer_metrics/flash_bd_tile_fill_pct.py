"""Flash kernels: pairs the block-diffusion mask keeps over the pairs of the tiles its grids compute, at the step's rows, from the program's own count at trace time (``hvdtpu_spmd_flash_pairs_total`` with ``mask="block_diffusion"``: ``kept`` over ``computed``, every kernel); about 80% at 8192 data tokens, blocks of 4 and 1024-wide tiles (the eight noised-noised diagonal tiles are 0.4% full, the sixteen diagonal tiles of the other two parts about half): what a tiling that follows the mask's blocks would move. None where the program counts no such pairs."""

from benchmarks import program_counters

FAMILY = "hvdtpu_spmd_flash_pairs_total"
PAD = 128       # the kernels pad a sequence to this many rows


def read(ctx):
    seq = str(-(-2 * ctx.job.seq // PAD) * PAD)
    kept, computed = (program_counters.value(
        FAMILY, mask="block_diffusion", pairs=pairs, seq=seq)
        for pairs in ("kept", "computed"))
    if not kept or not computed:
        return None
    return 100.0 * kept / computed
