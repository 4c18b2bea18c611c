"""Training loop: how far the untraced window's rate moved between its first and its last third of segments, in % of the first (``context.window_drift``, unsigned; ``run.py``'s window line prints it signed), in the cells that report ``tok_s_chip``. Traffic that is stationary reads the segments' own noise; a model that learns the ring of batches and moves its router reads percents."""

from benchmarks.context import window_drift


def read(ctx):
    drift = window_drift(ctx.rates)
    return None if drift is None else 100.0 * abs(drift)
