"""Compiled step: instructions XLA's rematerialisation pass made again in the job's step (its clones' names end ``.remat``, ``.remat<n>``, ``.remat_compressed``), from the program's own report of its executable (``hvd.compiled_step_report``); 0 where the schedule had memory to spare."""

import json
import time


def report(ctx):
    """The program's report of the job's compiled step, asked for here, after
    the window (the first reader pays for it, the program keeps it); None
    where the program has no such call."""
    import horovod_tpu as hvd

    ask = getattr(hvd, "compiled_step_report", None)
    if ask is None:
        return None
    t0 = time.perf_counter()
    made = ask(ctx.job.step)
    took = time.perf_counter() - t0
    if took >= made["seconds"]:     # made now, not kept: what it cost, found
        print(f"step report: asked for in {took:.3f} s, made in "
              f"{made['seconds']:.3f} s: " + json.dumps(
                  {k: made[k] for k in ("instructions", "rematerialized",
                                        "parameter_copies", "whiles",
                                        "collectives", "kernels",
                                        "memory_bytes")}), flush=True)
    return made


def read(ctx):
    made = report(ctx)
    return None if made is None else float(len(made["rematerialized"]))
