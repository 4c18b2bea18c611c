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

    @functools.wraps(fn)
    def traced(*a):
        # Trace time again: what names this executable to
        # compiled_step_report, the arguments as shapes with the shardings
        # in_specs and the mesh give them.
        traced.shapes = jax.tree.map(
            lambda spec, sub: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=NamedSharding(m, spec),
                    weak_type=x.aval.weak_type)
                if isinstance(x, jax.core.Tracer) else x, sub),
            in_specs, a, is_leaf=lambda spec: isinstance(spec, P))
        return mapped(*a)
    return jax.jit(traced, donate_argnums=tuple(donate_argnums),
                   static_argnums=static_argnums)


def compiled_step_report(step) -> dict:
    """What the compiler made of a :func:`run_step` function that has run, as
    last traced: ``memory_bytes`` (``memory_analysis()``: arguments, outputs,
    aliased, temporaries, generated_code) and, from the optimized HLO's text,
    ``rematerialized`` (the instructions XLA made again when short of memory,
    each with ``name``, ``opcode``, result ``bytes`` and the program's
    ``op_name``), ``parameter_copies`` (``count``, ``bytes``), ``whiles``,
    ``collectives`` by kind as the combiner left them, ``kernels`` (Mosaic
    calls by name), ``kernel_calls`` (each of those calls with the pass and
    the scope of the program it runs in, the grouped matmuls XLA names itself
    included: ``hlo_report.reduce_hlo``), ``instructions`` and the ``seconds``
    the report took.

    It lowers and compiles on the traced shapes, placed as ``in_specs`` says:
    JAX returns the executable the step runs from its caches (no compile, no
    new entry in ``step._cache_size()``). A caller that fed un-placed state
    first has two executables (docs/metrics.md): this is the placed one's.
    Made when asked, kept until the step is traced anew, and from then on
    shown by ``hvd.metrics()`` (``hvdtpu_spmd_step_*``); never on a step's
    path.
    """
    recorder = runtime.recorder()
    shapes = getattr(getattr(step, "__wrapped__", None), "shapes", None)
    if recorder is None or shapes is None:
        raise ValueError(
            "compiled_step_report: a hvd.run_step function that has run "
            "under hvd.init() in SPMD mode, got "
            f"{getattr(step, '__name__', step)!r}")
    return recorder.step_report(step.__name__, shapes,
                                lambda: step.lower(*shapes).compile())


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


@functools.lru_cache(maxsize=32)
def _restorer(shape: tuple, sharding: NamedSharding):
    """The device half of :func:`shard_batch`'s flat path: ``[N, rest]`` back
    to ``shape`` in ``sharding``, the flat array donated (no second batch is
    held). One jitted function a shape and sharding, compiled once a dtype."""
    def restore_batch_shape(flat):      # the name a trace and a span show
        return flat.reshape(shape)
    return jax.jit(restore_batch_shape, out_shardings=sharding,
                   donate_argnums=0)


def _crosses_flat(x, dim: int, sharding: NamedSharding) -> bool:
    """Whether ``x`` is a host leaf whose bytes can cross in their own order:
    a C-contiguous ``numpy`` array of rank 3 or more that is not empty, split
    on its leading dimension over devices that are all this process's."""
    return (dim == 0 and isinstance(x, np.ndarray) and x.ndim >= 3
            and x.size > 0 and x.flags.c_contiguous
            and sharding.is_fully_addressable)


def shard_batch(batch, dim: int = 0, axis: Optional[str] = None, mesh=None):
    """Place a host batch onto the mesh, sharded on ``dim`` over the DP axis.

    The TPU-native replacement for per-rank data loading: one host feeds the whole
    mesh (or its local slice under multi-host jax).

    The device's layout of a leaf of rank 3 or more need not be its bytes'
    order (a v5e keeps a uint8 NHWC batch with N on the lanes), and the
    runtime makes that layout on the host before a byte crosses: 40 to 50 ms
    a 38.5 MB batch, which a chip with an empty queue waits for, against 8
    (``scripts/place_time.py``). Such a leaf (``_crosses_flat``) crosses as
    its ``[N, rest]`` view, whose layout is its own order, and takes its
    shape on the device (``_restorer``): the same shape, dtype, sharding and
    layout as ``jax.device_put(x, sharding)`` gives, which is what every
    other leaf gets.
    """
    m = mesh if mesh is not None else runtime.mesh()
    sharding = NamedSharding(m, batch_spec(dim, axis))
    # Counted always (four additions, no lock); timed, and watched until every
    # chip has the batch, only while a timeline runs (hvd.start_timeline).
    recorder = runtime.recorder()
    timed = recorder is not None and recorder.spans is not None
    t0 = time.time_ns() if timed else 0
    leaves, treedef = jax.tree.flatten(batch)
    flat = [_crosses_flat(x, dim, sharding) for x in leaves]
    placed = jax.tree.unflatten(treedef, [
        _restorer(x.shape, sharding)(jax.device_put(
            x.reshape(x.shape[0], -1), sharding))
        if is_flat else jax.device_put(x, sharding)
        for x, is_flat in zip(leaves, flat)])
    if recorder is not None:
        recorder.note_placed(sum(getattr(x, "nbytes", 0) for x in leaves),
                             leaves=len(leaves), flat=sum(flat))
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
