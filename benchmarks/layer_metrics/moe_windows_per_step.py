"""Expert layer: windows of ``moe.share_rows`` rows the layers' forward rules took a traced step on chip 0, summed over layers (``parallel/moe.py::_windows``: one a layer while the held experts' rows fit one, ``ceil(held rows / window)`` where the routing sends more); says which regime of a drifting router the other ``moe_*`` trace metrics saw."""

import collections

from benchmarks import scope_reduce, trace_reduce


def _place(op_name: str, cls: str):
    """``(scopes down to "windows", "first" | "loop" | "body")`` of a forward
    instruction under the program's window scopes, else None: the window at 0
    is under ``.../windows/window``, the loop itself is ``.../windows/while``
    and a window of its body under ``.../windows/while/body/window``."""
    scopes = scope_reduce.scope_of(op_name)
    if cls != "forward" or "windows" not in scopes:
        return None
    at = scopes.index("windows")
    layer, inner = tuple(scopes[:at]), scopes[at + 1:]
    if not inner:
        return (layer, "loop") if op_name.endswith("/while") else None
    if "window" not in inner:
        return None
    return layer, "first" if inner[0] == "window" else "body"


def windows_taken(ops, names: dict) -> dict:
    """``(scopes down to "windows", in the loop's body?) -> executions`` over
    ``ops`` (sorted by start), for the forward rules alone (class
    ``forward``: not the backward rule's windows, which make the forward
    again, nor a recomputed copy's).

    A device event is one run of one instruction, and an instruction of a
    loop's body runs once a turn: its events are the turns the loop took
    (and those of one of the window at 0, the steps). No instruction is
    singled out, since which survive fusion is the compiler's choice: each
    is counted by itself, and the count most of a group's instructions share
    is the group's. **A body's instruction counts only while its loop runs**
    (the ``while`` has an event of its own that spans its turns): the
    compiler lifts what does not depend on the turn out of the loop (a
    reshape of the routing weights), and that runs once a step, just before
    the loop, whether the body runs or not; counted, it read a window too
    many for each layer whose loop never ran (my chip run, PR 33). The
    grouped-matmul kernels are no better a mark: their ``op_name`` keeps the
    scopes in the cell's step and loses them in a layer run alone."""
    places: dict = {}
    events: dict = collections.defaultdict(collections.Counter)
    loop_end: dict = {}         # layer -> when its loop that began last ends
    for op in ops:
        if op.name not in places:
            places[op.name] = _place(*names.get(op.name, ("", "unscoped")))
        if places[op.name] is None:
            continue
        layer, kind = places[op.name]
        if kind == "loop":
            loop_end[layer] = op.end
        elif kind == "first" or op.start < loop_end.get(layer, 0.0):
            events[(layer, kind == "body")][op.name] += 1
    return {group: collections.Counter(per.values()).most_common(1)[0][0]
            for group, per in events.items()}


def read(ctx):
    if not ctx.has_device_trace():
        return None
    path = scope_reduce.newest_xplane()
    names = scope_reduce.program_names(path) if path else {}
    lo, hi = trace_reduce.window_of(ctx.trace)
    taken = windows_taken(
        [op for op in trace_reduce.first_device(ctx.trace)
         if lo <= op.start < hi], names)
    return sum(taken.values()) / ctx.steps_traced if taken else None
