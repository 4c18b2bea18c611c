"""What the program counts about itself, read through ``hvd.metrics()``.

In SPMD mode ``hvd.metrics()`` gives the ``hvdtpu_spmd_*`` families of the
runtime's recorder (``horovod_tpu/spmd_recorder.py``, docs/metrics.md): what
JAX traced, lowered and compiled for how long, by function; the persistent
cache's hits and misses; when ``hvd.init()`` was done. A program without the
recorder gives no family, and the reader then returns None.
"""

from __future__ import annotations


def value(family: str, **labels):
    """The sum of a family's samples whose labels include ``labels``; None
    where the program has no such family or no such sample."""
    import horovod_tpu as hvd

    samples = [v for _, have, v in hvd.metrics().get(family, {}).get(
        "samples", []) if all(have.get(k) == w for k, w in labels.items())]
    return sum(samples) if samples else None


def step_seconds(ctx, *stages: str):
    """Seconds JAX spent on the job's step function in the given stages
    (``trace``, ``lower``, ``backend_compile``: the last is a compile or a
    load from the persistent cache)."""
    function = getattr(ctx.job.step, "__name__", None)
    parts = [value("hvdtpu_spmd_compile_seconds_total", function=function,
                   stage=stage) for stage in stages]
    return None if None in parts else sum(parts)
