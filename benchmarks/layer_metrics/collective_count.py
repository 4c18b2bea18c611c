"""Gradient exchange: collective instructions in the job's compiled step, all kinds (all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute; a start/done pair once), from the program's own report: what XLA's combiner left of the gradients' and the batch statistics' all-reduces."""

from benchmarks.layer_metrics import tok_compiler_remat


def read(ctx):
    made = tok_compiler_remat.report(ctx)
    return None if made is None else float(sum(made["collectives"].values()))
