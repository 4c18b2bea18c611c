"""Compiled step: what the compiler reckons the job's step holds at once on a chip, ``memory_analysis()``'s arguments + temporaries + outputs - aliased (outputs written where donated arguments were) from the program's own report; the compiler refuses a step above 15.75 GiB on a v5e whatever the allocator would say (``tok_peak_hbm_gib``)."""

from benchmarks.layer_metrics import tok_compiler_remat


def read(ctx):
    made = tok_compiler_remat.report(ctx)
    if made is None:
        return None
    m = made["memory_bytes"]
    return (m["arguments"] + m["temporaries"] + m["outputs"]
            - m["aliased"]) / 2 ** 30
