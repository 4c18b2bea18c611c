"""Selective-scan layer: device time per step, on chip 0, of every operation under an ``s6`` scope (``models/decoder/mixers/s6.py``: the mixer's norm, ``in_proj``, ``conv`` with its ``hvd_conv_*`` kernels, ``x_proj``, ``scan`` with the ``hvd_s6_*`` kernels, ``gate``, ``out_proj``; forward, recomputed and backward)."""

import re

from benchmarks import scope_reduce, trace_reduce


def scope_ms(ctx, outer, inner=(), kernel=None):
    """ms a traced step of the operations whose scopes hold one of ``outer``
    and, after it, one of ``inner`` (any, if empty), each operation once;
    with ``kernel`` (a pattern), an operation of that name counts whatever
    its scopes say. None where the trace holds none (a program without such
    a layer, as the parent commit's; a trace with no device plane or without
    the program's names). A kernel keeps the program's scopes in its
    ``op_name``, so it is found as any other operation is."""
    if not ctx.has_device_trace():
        return None
    named = re.compile(kernel) if kernel else None
    path = scope_reduce.newest_xplane()
    names = scope_reduce.program_names(path) if path else {}
    lo, hi = trace_reduce.window_of(ctx.trace)
    seconds, found = 0.0, False
    for op in trace_reduce.first_device(ctx.trace):
        scopes = scope_reduce.scope_of(names.get(op.name, ("", ""))[0])
        at = next((scopes.index(s) for s in outer if s in scopes), None)
        hit = at is not None and (
            not inner or any(s in scopes[at + 1:] for s in inner))
        if hit or (named and named.search(op.name)):
            found = True
            seconds += trace_reduce.total(
                trace_reduce.clip([(op.start, op.end)], lo, hi))
    return 1e3 * seconds / ctx.steps_traced if found else None


def read(ctx):
    return scope_ms(ctx, ("s6",))
