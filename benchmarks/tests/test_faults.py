"""``correct`` comes out false when the timed path is broken underneath the
harness: the whole of a rehearsal run (``run.main``, the look for a chip
skipped by ``--rehearsal``) of one cell for each job that BENCHMARK.json's
configurations name, with one fault planted in the program the job is
written against, once for each fault a training cell can have. The cells
are read from the files when the tests are collected: a job that a later PR
brings with its configuration is a case here without an edit.

On the chip, at a cell's own size (no test; the whole run with the fault
planted, its result line printed):

    python3 benchmarks/tests/test_faults.py <cell> <fault> <seed> <seconds>
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Each is planted before the job is built; run.py and the job are untouched.
# Beside it, the row of ``compared`` that is for this fault.
FAULTS = {
    # A step that returns its parameters unchanged: their change reads
    # nothing for the reference's.
    "state_unchanged": (
        "import optax\n"
        "optax.apply_updates = lambda params, updates: params\n",
        "update norm"),
    # Half of the batch left out, the mean taken over the rest: every other
    # target of the decoders' (a rehearsal's check has one sequence a chip)
    # and every other image's loss.
    "half_batch": (
        "import jax.numpy as jnp\n"
        "import optax\n"
        "from horovod_tpu.models import gpt\n"
        "def every_other(x):\n"
        "    return jnp.arange(x.size).reshape(x.shape) % 2 == 0\n"
        "whole = gpt.loss_and_aux\n"
        "gpt.loss_and_aux = lambda params, tokens, targets, *rest: whole(\n"
        "    params, tokens, jnp.where(every_other(targets), targets, -1),\n"
        "    *rest)\n"
        "each = optax.softmax_cross_entropy_with_integer_labels\n"
        "optax.softmax_cross_entropy_with_integer_labels = \\\n"
        "    lambda logits, labels: jnp.where(\n"
        "        every_other(labels), 2 * each(logits, labels), 0)\n",
        "gradient norm after the exchange"),
    # The exchange between chips left out: every chip steps on its own
    # shard's gradient.
    "no_exchange": (
        "import horovod_tpu as hvd\n"
        "hvd.DistributedOptimizer = lambda opt, **k: opt\n",
        "gradient norm after the exchange"),
    # The learning rate another than the configuration states.
    "other_rate": (
        "import optax\n"
        "adamw, sgd = optax.adamw, optax.sgd\n"
        "optax.adamw = lambda lr, **k: adamw(2 * lr, **k)\n"
        "optax.sgd = lambda lr, **k: sgd(2 * lr, **k)\n",
        "update norm"),
}
DRIVER = """
import sys
sys.path.insert(0, {root!r})
{fault}
from benchmarks import run
sys.exit(run.main({args!r}))
"""


def a_cell_for_each_job() -> list:
    """The first cell of BENCHMARK.json for each job its configurations'
    rehearsal twins name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {}
    for cell in bench["workloads"]:
        entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
        with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                               os.path.basename(entry["file"]))) as f:
            cells.setdefault(json.load(f)["job"], cell["name"])
    return sorted(cells.values())


def run_with(cell: str, fault: str, seed: int = 2147483999,
             seconds: str = "1", rehearsal: bool = True):
    args = ["--workload", cell, "--seed", str(seed), "--seconds", seconds,
            "--trace", "0"] + ["--rehearsal"] * rehearsal
    done = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(root=ROOT, fault=fault, args=args)],
        capture_output=True, text=True, timeout=1200, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1]), done.stderr


@pytest.mark.parametrize("cell", a_cell_for_each_job())
def test_the_sound_program_is_correct(cell):
    result, errors = run_with(cell, "")
    assert result["correct"] is True
    for what, row in result["compared"].items():
        assert row["off_by"] <= row["limit"], what
        assert f"compared: {what}: off by" in errors


@pytest.mark.parametrize("name", sorted(FAULTS))
@pytest.mark.parametrize("cell", a_cell_for_each_job())
def test_a_planted_fault_is_not_correct(cell, name):
    fault, row = FAULTS[name]
    result, _ = run_with(cell, fault)
    assert result["correct"] is False
    # The row that is for this fault fails by far.
    compared = result["compared"][row]
    assert compared["off_by"] > 3 * compared["limit"], compared


if __name__ == "__main__":
    cell, name, seed, seconds = sys.argv[1:]
    line, _ = run_with(cell, FAULTS[name][0] if name != "sound" else "",
                       int(seed), seconds, rehearsal=False)
    print(json.dumps({"cell": cell, "fault": name, "seed": int(seed),
                      "correct": line["correct"],
                      "compared": line["compared"]}), flush=True)
