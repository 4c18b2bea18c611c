"""Linear-attention layer: device time per step, on chip 0, of every operation under a ``gdn`` scope (``models/gpt.py``; the mixer's norm, ``in_proj``, ``conv``, ``scan``, ``gate_norm``, ``out_proj``; forward, recomputed and backward) and of any scan kernel of the program's own (``hvd_gdn_*``)."""

import re

from benchmarks import scope_reduce, trace_reduce


def scope_ms(ctx, inner=(), kernels=True):
    """ms a traced step of the operations whose scopes hold ``gdn`` and,
    after it, one of ``inner`` (any, if empty), plus the kernels the job
    names under ``kernel_costs["gdn_scan"]`` if ``kernels``; None where the
    trace holds neither (a program with no linear-attention layer, as the
    parent commit's, a trace with no device plane)."""
    if not ctx.has_device_trace():
        return None
    named = ctx.job.kernel_costs.get("gdn_scan")
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
            after = scopes[scopes.index("gdn") + 1:] if "gdn" in scopes \
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
