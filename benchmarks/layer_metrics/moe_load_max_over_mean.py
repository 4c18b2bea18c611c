"""Expert layer: the busiest expert's tokens over the mean, the largest over the layers, from the counts the layer returned for the check's sample (1.0 is a perfectly even routing; the grouped matmuls' time follows the sum, a sharded layer's the maximum)."""


def read(ctx):
    counts = getattr(ctx.job, "expert_counts", None)
    if counts is None:
        return None
    return float((counts.max(axis=-1) / counts.mean(axis=-1)).max())
