"""Expert layer: device time per step, on chip 0, of the grouped-matmul kernels that make a forward product again: in a checkpointed block's recomputed copy (the gate and up products) and in a windowed layer's backward rule (each window's forward, ``parallel/moe.py::_held_experts_bwd``), as the compiled step's report places them (``pass`` ``recomputation``)."""

from benchmarks.layer_metrics.moe_experts_fwd_ms import pass_ms


def read(ctx):
    return pass_ms(ctx, "recomputation")
