"""Compiled step: Mosaic kernels of the job's step that the program's own report (``hvd.compiled_step_report``'s ``kernel_calls``) can place in no pass, neither by their own name nor by what they read; 0 unless XLA or a new kernel left one without a name, whose time a trace split by pass then leaves out."""

from benchmarks.layer_metrics import tok_compiler_remat


def read(ctx):
    made = tok_compiler_remat.report(ctx)
    calls = None if made is None else made.get("kernel_calls")
    return None if calls is None else float(
        sum(call["pass"] == "none" for call in calls))
