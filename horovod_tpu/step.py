"""Compiled-step helpers: shard_map + jit over the global mesh.

No direct reference analog — this is the TPU-native replacement for the
reference's implicit execution model (each process runs the framework's own
graph/eager engine; Horovod only intercepts gradients). On TPU the training step is
a single SPMD program over the device mesh; these helpers wrap ``jax.shard_map`` /
``jax.jit`` with the runtime's mesh so user code matches Horovod's ergonomics:

    step = hvd.run_step(train_step, in_specs=(hvd.REPLICATED, hvd.REPLICATED,
                                              hvd.batch_spec()),
                        out_specs=hvd.REPLICATED, donate_argnums=(0, 1))
"""

from __future__ import annotations

import functools
import time
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import runtime

REPLICATED = P()


def batch_spec(dim: int = 0, axis: Optional[str] = None) -> P:
    """PartitionSpec sharding array dim ``dim`` over the data-parallel axis."""
    ax = axis if axis is not None else runtime.dp_axis()
    entries: list = [None] * (dim + 1)
    entries[dim] = ax
    return P(*entries)


def run_step(fn=None, *, in_specs, out_specs, mesh=None,
             donate_argnums: Sequence[int] = (), static_argnums=(),
             check_vma: bool = True):
    """shard_map ``fn`` over the global mesh and jit the result.

    Inside ``fn``, all :mod:`horovod_tpu` collectives lower to XLA collectives on
    ICI (``hvd.allreduce`` → ``lax.psum`` etc.), and ``hvd.rank_in_step()`` /
    ``hvd.size_in_step()`` give per-device rank/size.
    """
    if fn is None:
        return functools.partial(run_step, in_specs=in_specs,
                                 out_specs=out_specs, mesh=mesh,
                                 donate_argnums=donate_argnums,
                                 static_argnums=static_argnums,
                                 check_vma=check_vma)
    m = mesh if mesh is not None else runtime.mesh()
    from .ops.collectives import _plain_semantics

    @functools.wraps(fn)
    def body(*a, **k):
        # Runs only while JAX traces the step, never on a step's path. The
        # recorder learns why this trace happened (first call, new shapes,
        # new shardings); without varying-axes tracking the collectives
        # can't see invariance, so plain (Horovod-exact) semantics are
        # flagged for the duration of the trace.
        recorder = runtime.recorder()
        if recorder is not None:
            recorder.note_trace(getattr(fn, "__name__", str(fn)), tuple(
                (getattr(x, "shape", None), str(getattr(x, "dtype", None)))
                for x in jax.tree.leaves((a, k))))
        prev = getattr(_plain_semantics, "on", False)
        _plain_semantics.on = prev or not check_vma
        try:
            return fn(*a, **k)
        finally:
            _plain_semantics.on = prev
    mapped = jax.shard_map(body, mesh=m, in_specs=in_specs,
                           out_specs=out_specs, check_vma=check_vma)
    return jax.jit(mapped, donate_argnums=tuple(donate_argnums),
                   static_argnums=static_argnums)


def data_parallel_step(train_step, donate_state: bool = True,
                       batch_dim: int = 0, mesh=None):
    """Convenience wrapper for the canonical DP signature
    ``train_step(params, opt_state, batch) -> (params, opt_state, aux)``:
    params/opt_state replicated, batch sharded on ``batch_dim``. The gradient
    allreduce inside (via :func:`DistributedOptimizer` or
    :func:`allreduce_gradients`) makes the outputs replicated.
    """
    specs_in = (REPLICATED, REPLICATED, batch_spec(batch_dim))
    return run_step(train_step, in_specs=specs_in, out_specs=REPLICATED,
                    mesh=mesh,
                    donate_argnums=(0, 1) if donate_state else ())


def shard_batch(batch, dim: int = 0, axis: Optional[str] = None, mesh=None):
    """Place a host batch onto the mesh, sharded on ``dim`` over the DP axis.

    The TPU-native replacement for per-rank data loading: one host feeds the whole
    mesh (or its local slice under multi-host jax).
    """
    m = mesh if mesh is not None else runtime.mesh()
    sharding = NamedSharding(m, batch_spec(dim, axis))
    # Counted always (two additions, no lock); timed, and watched until every
    # chip has the batch, only while a timeline runs (hvd.start_timeline).
    recorder = runtime.recorder()
    timed = recorder is not None and recorder.spans is not None
    t0 = time.time_ns() if timed else 0
    placed = jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
    if recorder is not None:
        recorder.note_placed(sum(getattr(x, "nbytes", 0)
                                 for x in jax.tree.leaves(batch)))
    if timed:
        t1 = time.time_ns()
        recorder.span("shard_batch", t0, t1)
        recorder.watch_batch(placed, t1)
    return placed


def replicate(tree, mesh=None):
    """Place a host pytree onto the mesh fully replicated."""
    m = mesh if mesh is not None else runtime.mesh()

    def _put(x):
        return jax.device_put(x, NamedSharding(m, P()))

    return jax.tree.map(_put, tree)
