"""Expert layer: device time per step of the operations under ``moe/router/groups`` (the choice limited to groups: a group's two largest leaning scores, the best groups, the mask; ``parallel/moe.py::kept_groups``), all passes; None where no layer's router chooses in groups."""

from benchmarks.layer_metrics.s6_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, ("moe",), inner=("groups",))
