"""The faults that are the looped job's own, planted as ``test_faults.py``
plants its four (which run on this job's cell too: it is a case there by its
configuration's ``job``), each before the job is built, ``run.py`` and the
job untouched; ``correct`` must come out false by the row that is for it:

* **the cotangent for ``weights`` dropped**: the head's rule hands the exit
  probabilities nothing, and the gate learns from the entropy alone. The
  gate is 2049 of half a billion parameters: the gradient's norm sees what
  the hidden states lose, not the gate, and the gate's own row must.
* **``N_out`` left out between passes**: the next pass starts from the
  stream the norm read, not from its output (the later passes' rows show it
  on some seeds, the gradient's norm on all: on the chip 11 and 23 times its
  limit).
* **the last pass given ``lambda^T`` times the product** where it takes the
  mass that is left.
* **a pass dropped**: the stack runs one pass fewer and the last state
  stands in twice (on the chip's 5115 targets the gradient's norm holds it,
  the fourth pass's row on some seeds only).

On the chip, at the cell's own size (no test; the whole run with the fault
planted, its result line printed):

    python3 benchmarks/tests/test_loop_faults.py <cell> <fault>[,<fault>...] <seed> <seconds>
"""

import json
import sys

import pytest

from test_faults import run_with

CELL = "ouro-2.6b_s4096"
GATE_ROW = "exit gate's gradient norm"
FAULTS = {
    "weights_cotangent_dropped": ("""
from jax import lax
from horovod_tpu.models import gpt
whole = gpt._head_loss_rows
gpt._head_loss_rows = lambda x, w, targets, weights, *rest: whole(
    x, w, targets, lax.stop_gradient(weights), *rest)
""", GATE_ROW),
    # The loop's own line, taken out of its source: the stream stays what
    # the norm read.
    "norm_left_out_between_passes": ("""
import inspect
from horovod_tpu.models import gpt
source, sound = inspect.getsource(gpt._passes), "x = states[-1]\\n"
assert source.count(sound) == 1, "the loop no longer carries its state so"
exec(source.replace(sound, "pass\\n"), vars(gpt))
""", "gradient norm after the exchange"),
    # ``_exit_distribution``'s signature; a token's ``p`` add up to less
    # than 1.
    "last_pass_gated": ("""
import jax
import jax.numpy as jnp
from horovod_tpu.models import gpt
def gated(score):
    lam = jax.nn.sigmoid(score)
    left = jnp.concatenate([jnp.ones_like(lam[:1]),
                            jnp.cumprod(1.0 - lam, axis=0)[:-1]])
    p = lam * left
    return p, -jnp.sum(p * jnp.log(p), axis=0)
gpt._exit_distribution = gated
""", "loss"),
    "pass_dropped": ("""
import dataclasses
from horovod_tpu.models import gpt
whole = gpt._passes
def short(params, tokens, positions, cfg):
    states, auxes = whole(params, tokens, positions, dataclasses.replace(
        cfg, loop_passes=cfg.loop_passes - 1))
    return states + states[-1:], auxes
gpt._passes = short
""", "pass 4's mean cross-entropy"),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_fault_of_the_loop_is_not_correct(name):
    fault, row = FAULTS[name]
    result, _ = run_with(CELL, fault)
    assert result["correct"] is False
    compared = result["compared"][row]
    assert compared["off_by"] > 3 * compared["limit"], compared


if __name__ == "__main__":
    cell, names, seed, seconds = sys.argv[1:]
    # Each fault a run of its own, one after the other, a seed each.
    for n, name in enumerate(names.split(",")):
        line, _ = run_with(cell, FAULTS[name][0], int(seed) + n, seconds,
                           rehearsal=False)
        print(json.dumps({"cell": cell, "fault": name, "seed": int(seed) + n,
                          "correct": line["correct"],
                          "compared": line["compared"]}), flush=True)
