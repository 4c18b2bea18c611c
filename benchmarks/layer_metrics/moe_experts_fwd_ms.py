"""Expert layer: device time per step, on chip 0, of the grouped-matmul kernels the forward pass runs: the events whose name is an instruction of the compiled step's report (``hvd.compiled_step_report``'s ``kernel_calls``) with the job's grouped-matmul match and ``pass`` ``forward``. XLA names these kernels itself; the report places them by what they read."""

import re

from benchmarks import trace_reduce
from benchmarks.layer_metrics import tok_compiler_remat


def pass_ms(ctx, which: str):
    """ms a traced step of the job's grouped-matmul kernels that the
    program's report places in the pass ``which``; None where there is no
    device trace, the job names no such kernel, the program's report places
    none (the parent's has no ``kernel_calls``) or the trace holds none of
    them. The three passes add up to the kernels' part of
    ``moe_experts_ms``."""
    named = ctx.job.kernel_costs.get("grouped_matmul")
    if not ctx.has_device_trace() or not named:
        return None
    made = tok_compiler_remat.report(ctx)
    kernel = re.compile(named["match"])
    passes = {call["instruction"]: call["pass"]
              for call in (made or {}).get("kernel_calls", ())
              if kernel.search(call["instruction"])}
    lo, hi = trace_reduce.window_of(ctx.trace)
    seconds, found = 0.0, False
    for op in trace_reduce.first_device(ctx.trace):
        if op.name in passes:
            found = True
            if passes[op.name] == which:
                seconds += trace_reduce.total(
                    trace_reduce.clip([(op.start, op.end)], lo, hi))
    return 1e3 * seconds / ctx.steps_traced if found else None


def read(ctx):
    return pass_ms(ctx, "forward")
