"""Looped stack: device time per step, on chip 0, of every operation under a ``head`` or ``loss`` scope (``models/gpt.py``: the norm at the end of every pass, the passes' rows through the head's one rule, ``_head_loss``: the logits' product, the cross-entropy, the two gradient products, every block of T x the rows), forward and backward. None where the trace holds no such scope."""

from benchmarks.layer_metrics.s6_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, ("head", "loss"))
