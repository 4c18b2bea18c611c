"""Flash kernels: device time per step of the three kernels of the window attention layers alone, told from the full layers' by the scope they run under (``attn_window`` where a full layer has ``attn``: ``models/gpt.py::_block``), forward, dK/dV and dQ."""

import re

from benchmarks import scope_reduce, trace_reduce

KERNEL = re.compile(r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$")
SCOPE = "attn_window"


def window_kernel_seconds(ops, names: dict, lo: float, hi: float):
    """Summed seconds inside ``[lo, hi]`` of the flash kernels among ``ops``
    whose ``op_name`` (``names``: instruction -> (op_name, class)) lies under
    the window layers' scope; None where no flash kernel of ``ops`` has an
    ``op_name`` at all (a trace file without the program's names)."""
    seconds, named = 0.0, False
    for op in ops:
        if not KERNEL.match(op.name) or op.name not in names:
            continue
        named = True
        if SCOPE in scope_reduce.scope_of(names[op.name][0]):
            seconds += trace_reduce.total(
                trace_reduce.clip([(op.start, op.end)], lo, hi))
    return seconds if named else None


def read(ctx):
    if not ctx.has_device_trace():
        return None
    path = scope_reduce.newest_xplane()
    names = scope_reduce.program_names(path) if path else {}
    seconds = window_kernel_seconds(trace_reduce.first_device(ctx.trace),
                                    names, *trace_reduce.window_of(ctx.trace))
    return 1e3 * seconds / ctx.steps_traced if seconds else None
