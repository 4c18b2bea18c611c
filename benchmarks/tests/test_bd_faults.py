"""The fault that is the block-diffusion job's own, planted as
``test_faults.py`` plants its four (which run on this job's cell too: it is
a case there by its configuration's ``job``): **the noised rows allowed
their own clean block**, ``bk <= bq`` in the mask's second clause. A noised
row then reads the token it is to predict off a clean key: a trained model's
loss collapses, and at a seeded checkpoint the keys' and values' gradients
turn towards that leak. ``correct`` must come out false by the row that
holds the mask.

On the chip, at the cell's own size (no test; the whole run with the fault
planted, its result line printed):

    python3 benchmarks/tests/test_bd_faults.py <cell> <seed> <seconds>
"""

import json
import sys

from test_faults import run_with

CELL = "sdar-30b-a3b-chat_s8192"
ROW = ("key and value gradients along the reference's, the targets of the "
       "first 8 blocks alone")
# Planted before the job is built: the pairs the kernels keep, with the
# second clause one block too wide (the faulty ``keep`` lives beside the
# reference; ``scripts/check_sweep.py --variant bd_own_clean_block`` plants
# the same function).
OWN_CLEAN_BLOCK = """
from horovod_tpu.ops import flash_attention as fa
from benchmarks.reference.gpt_bd_moe_dp import own_clean_block_keep
fa.Mask.keep = own_clean_block_keep
"""


def test_the_own_clean_block_leak_is_not_correct():
    result, _ = run_with(CELL, OWN_CLEAN_BLOCK)
    assert result["correct"] is False
    compared = result["compared"][ROW]
    assert compared["off_by"] > 3 * compared["limit"], compared


if __name__ == "__main__":
    cell, seed, seconds = sys.argv[1:]
    line, _ = run_with(cell, OWN_CLEAN_BLOCK, int(seed), seconds,
                       rehearsal=False)
    print(json.dumps({"cell": cell, "fault": "own_clean_block",
                      "seed": int(seed), "correct": line["correct"],
                      "compared": line["compared"]}), flush=True)
