"""Latent attention layer: device time per step, on chip 0, of every operation under the ``attn`` scope of a layer that holds an MLA mixer (``models/gpt.py``: the sublayer's norm, ``mla_proj``, ``mla_rope``, the flash kernels; forward, recomputed and backward). An MLA layer is told from a plain attention layer by its own scopes: a layer some operation of which lies under ``mla_proj`` or ``mla_rope``."""

import re

from benchmarks import scope_reduce, trace_reduce

OUTER = "attn"
OWN = ("mla_proj", "mla_rope")
LAYER = re.compile(r"^layer\d+$")


def scope_ms(ctx, inner=()):
    """ms a traced step of the operations whose scopes hold an MLA layer's
    ``layer<i>``, ``attn`` after it and, after that, one of ``inner`` (any,
    if empty); None where the trace holds no MLA layer (a program without
    one, as the parent commit's; a trace with no device plane or without
    the program's names). The flash kernels keep the program's scopes in
    their ``op_name``, so they are found as any other operation is."""
    if not ctx.has_device_trace():
        return None
    path = scope_reduce.newest_xplane()
    names = scope_reduce.program_names(path) if path else {}
    lo, hi = trace_reduce.window_of(ctx.trace)
    under = []      # (layer, the scopes after attn, seconds in the window)
    for op in trace_reduce.first_device(ctx.trace):
        scopes = scope_reduce.scope_of(names.get(op.name, ("", ""))[0])
        layer = next(filter(LAYER.match, scopes), None)
        if OUTER not in scopes or layer is None:
            continue
        under.append((layer, scopes[scopes.index(OUTER) + 1:],
                      trace_reduce.total(trace_reduce.clip(
                          [(op.start, op.end)], lo, hi))))
    layers = {layer for layer, after, _ in under
              if any(s in after for s in OWN)}
    hits = [seconds for layer, after, seconds in under if layer in layers
            and (not inner or any(s in after for s in inner))]
    return 1e3 * sum(hits) / ctx.steps_traced if hits else None


def read(ctx):
    return scope_ms(ctx)
