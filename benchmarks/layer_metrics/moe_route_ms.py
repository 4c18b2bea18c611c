"""Expert layer: device time per step of the operations under ``moe/router``, ``moe/dispatch`` and ``moe/combine`` (soft-max, top-k, the sort, the two row permutations, the weighted sum), all passes."""

from benchmarks.layer_metrics.moe_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, inner=("router", "dispatch", "combine"),
                    kernels=False)
