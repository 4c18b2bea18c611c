"""Looped stack: device time per step, on chip 0, of every operation under an ``exit`` scope (``models/gpt.py::loss_and_aux`` of a looped stack: the exit gate's product on every pass's normed rows, the exit distribution, its entropy and the sums the step reports of them), forward and backward. None where the program has no such scope (a stack that runs once, as every program before PR 69)."""

from benchmarks.layer_metrics.s6_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, ("exit",))
