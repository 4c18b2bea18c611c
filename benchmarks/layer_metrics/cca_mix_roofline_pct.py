"""CCA attention layer: least time the chip could take for the mixes the step asks for (``flops_cca.mix_pass_cost`` times the passes, the job's ``cca_mix_cost``: the larger of the grouped stage's operations over the MXU's peak and the bytes of ``u`` read and ``q``, ``k``, ``v`` written over the HBM's) over ``cca_mix_ms``."""

from benchmarks import flops
from benchmarks.layer_metrics import cca_mix_ms


def read(ctx):
    cost = getattr(ctx.job, "cca_mix_cost", None)
    ms = cca_mix_ms.read(ctx)
    if not cost or not ms:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx.peak)[0] / (ms * 1e-3)
