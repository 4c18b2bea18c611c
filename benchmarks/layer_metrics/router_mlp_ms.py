"""Expert layer: device time per step of the operations under ``moe/router`` of a step whose routers are MLPs (``models/gpt.py::_mlp_router``: the down-projection, the state from the layer before, the norm, the three matrices; then the soft-max, the choice and the counts), all passes. None where no router is one (the job says which kind its configuration has: ``job.cfg.router_kind``)."""

from benchmarks.layer_metrics.moe_ms import scope_ms


def read(ctx):
    if getattr(getattr(ctx.job, "cfg", None), "router_kind", None) != "mlp":
        return None
    return scope_ms(ctx, inner=("router",), kernels=False)
