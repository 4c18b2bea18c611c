"""Looped stack: block applications of one forward pass of the traced step, passes x layers, from the program's own count at trace time (``hvdtpu_spmd_loop_passes_total{passes, layers}``, noted by ``models/gpt.py::_passes`` where a stack runs more than once; 24 at 4 passes of 6 layers): **an invariant of the configuration to read the step's time against, not a quantity to optimise** (the entry's `lower` only says which way a fault moves it: a pass that is dropped shows here as well as in `correct`). None where the program counts no loop (a stack that runs once, or a program without the family)."""

import horovod_tpu as hvd

FAMILY = "hvdtpu_spmd_loop_passes_total"


def read(ctx):
    calls = {int(labels["passes"]) * int(labels["layers"])
             for _, labels, _ in hvd.metrics().get(FAMILY, {}).get(
                 "samples", [])}
    # One looped stack a job; two shapes of loop would be two numbers.
    return float(calls.pop()) if len(calls) == 1 else None
