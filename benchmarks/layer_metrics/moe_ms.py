"""Expert layer: device time per step, on chip 0, of every operation under a ``moe`` scope (``models/gpt.py``; router, dispatch, experts, combine; forward, recomputed and backward) and of the grouped-matmul kernels, which XLA names itself and strips of the program's scopes."""

import re

from benchmarks import scope_reduce, trace_reduce


def scope_ms(ctx, inner=(), kernels=True):
    """ms a traced step of the operations whose scopes hold ``moe`` and,
    after it, one of ``inner`` (any, if empty), plus the grouped-matmul
    kernels if ``kernels``; None where the trace holds neither.

    The kernels are those the job names under ``kernel_costs
    ["grouped_matmul"]``: XLA compiles ``lax.ragged_dot`` to kernels whose
    ``op_name`` is its own ("ragged-dot-none"), whatever scope the program
    called them under, so they are found by instruction name."""
    if not ctx.has_device_trace():
        return None
    named = ctx.job.kernel_costs.get("grouped_matmul")
    kernel = re.compile(named["match"]) if named else None
    path = scope_reduce.newest_xplane()
    names = scope_reduce.program_names(path) if path else {}
    lo, hi = trace_reduce.window_of(ctx.trace)
    seconds, found = 0.0, False
    for op in trace_reduce.first_device(ctx.trace):
        if kernel and kernel.search(op.name):
            hit = kernels
        else:
            scopes = scope_reduce.scope_of(names.get(op.name, ("", ""))[0])
            after = scopes[scopes.index("moe") + 1:] if "moe" in scopes \
                else None
            hit = after is not None and (
                not inner or any(s in after for s in inner))
        if hit:
            found = True
            seconds += trace_reduce.total(
                trace_reduce.clip([(op.start, op.end)], lo, hi))
    return 1e3 * seconds / ctx.steps_traced if found else None


def read(ctx):
    return scope_ms(ctx)
