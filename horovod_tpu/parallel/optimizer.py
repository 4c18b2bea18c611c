"""Distributed optimizer: data-parallel gradient reduction for optax.

Reference surface: ``horovod/torch/optimizer.py`` (``_DistributedOptimizer`` :32 —
per-parameter allreduce hooks, ``backward_passes_per_step`` accumulation,
``synchronize()``; factory :383) and TF's ``DistributedOptimizer`` /
``DistributedGradientTape`` (``horovod/tensorflow/__init__.py:290/:527``).

TPU-native redesign: instead of per-parameter autograd hooks firing async
allreduces that a background thread fuses, the whole gradient pytree is reduced
inside the compiled training step — ``DistributedOptimizer`` is an
``optax.GradientTransformation`` wrapper whose ``update`` allreduces gradients over
the data-parallel mesh axis before the inner transform runs. Under ``jit`` XLA
fuses/schedules these ``psum``s over ICI, which subsumes the reference's tensor
fusion + cycle machinery.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import runtime
from ..ops import collectives as C

# The scopes the two halves of an update compile under, in every
# instruction's ``op_name``: the gradient reduction (dense or compressed,
# quantize and dequantize kernels included) and the inner optimizer's update.
# A device trace is split by them (benchmarks/scope_reduce.py).
SCOPE_EXCHANGE = "hvd_exchange"
SCOPE_OPTIMIZER = "hvd_optimizer"


def allreduce_gradients(grads, op: C.ReduceOp = C.ReduceOp.AVERAGE,
                        compression=None, prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0,
                        axis: Optional[str] = None,
                        hierarchical: Optional[Tuple[str, str]] = None):
    """Allreduce a gradient pytree across the data-parallel axis.

    Functional analog of ``DistributedGradientTape.gradient``
    (reference ``horovod/tensorflow/__init__.py:509-527``): use directly after
    ``jax.grad`` when not using :func:`DistributedOptimizer`.

    ``hierarchical=(inner_axis, outer_axis)`` routes through
    :func:`~horovod_tpu.ops.collectives.hierarchical_allreduce_p` — reduce-
    scatter over the fast ICI axis, allreduce over the slow DCN axis,
    allgather back (reference: ``NCCLHierarchicalAllreduce``). In-step only.
    ``hierarchical=("auto", inner_axis, outer_axis)`` consults the measured
    calibration table (:func:`~horovod_tpu.parallel.strategy
    .autotune_hierarchical`; reference: the parameter manager's categorical
    hierarchical switch, ``parameter_manager.h:186``) keyed on the total
    gradient bytes, falling back to flat when uncalibrated. The choice is
    baked into the compiled program at trace time — calibrate once after
    ``init`` and *before* building the training step; re-calibration does
    not retrace already-compiled steps.
    """
    if hierarchical is not None and compression is not None:
        # Checked BEFORE the auto resolution: the auto-flat early return
        # must not silently drop a compressor the hierarchical route would
        # reject (behavior must not flip with calibration state).
        raise ValueError(
            "hierarchical allreduce does not take a compressor; use "
            "compressed_allreduce over the slow axis instead")
    if hierarchical is not None and len(hierarchical) == 3 and \
            hierarchical[0] == "auto":
        from .strategy import choose_hierarchical
        inner, outer = hierarchical[1], hierarchical[2]
        nbytes = sum(int(np.prod(g.shape)) * jnp.dtype(g.dtype).itemsize
                     for g in jax.tree.leaves(grads))
        if choose_hierarchical(inner, outer, nbytes):
            hierarchical = (inner, outer)
        else:
            # Flat: the fused two-axis all-reduce — the same fused-buffer
            # grouping as the hierarchical arm, so the runtime program
            # matches what the calibration's single-buffer flat arm timed.
            if not C.in_named_trace(inner):
                raise ValueError(
                    "hierarchical allreduce is in-step only: call inside "
                    "run_step/shard_map over a mesh with both axes")
            return _fused_two_axis_allreduce(grads, op, inner, outer,
                                             prescale_factor,
                                             postscale_factor, flat=True)
    if hierarchical is not None:
        if not C.in_named_trace(hierarchical[0]):
            raise ValueError(
                "hierarchical allreduce is in-step only: call inside "
                "run_step/shard_map over a mesh with both axes")
        inner, outer = hierarchical
        return _fused_two_axis_allreduce(grads, op, inner, outer,
                                         prescale_factor,
                                         postscale_factor)
    return C.grouped_allreduce(grads, name="grads", op=op,
                               compression=compression,
                               prescale_factor=prescale_factor,
                               postscale_factor=postscale_factor, axis=axis)


def _fused_two_axis_allreduce(grads, op, inner: str, outer: str,
                              prescale: float, postscale: float,
                              flat: bool = False):
    """One two-axis reduction per (dtype, vma-signature) group instead of
    one per leaf — for the hierarchical path and (``flat=True``) the
    calibrated-flat path, so the auto choice always dispatches the same
    fused-buffer program shape the calibration timed.

    Reference: ``FuseResponses`` (``controller.cc:686``) fuses co-negotiated
    same-dtype tensors into a single buffer so one collective moves them all
    — here the flattened group buffer crosses the fabric in one volley per
    group. Leaves are grouped by dtype (no silent upcasts) AND by per-axis
    vma invariance: fusing an already-reduced (invariant) leaf with varying
    ones would re-sum it. MIN/MAX/PRODUCT/ADASUM fall back to per-leaf
    (no flattened fused form).
    """
    def reduce_buffer(buf, inv_inner, inv_outer):
        if not flat or op == C.ReduceOp.ADASUM:
            # ADASUM ignores the calibrated-flat choice: adasum_p is a
            # single-axis algorithm (no tuple-axis form), and VHDD is
            # *defined* as sum within the fast axis + Adasum across the
            # slow one — the hierarchical program IS Adasum's shape
            # (round-4 advisor finding: the flat arm forwarded ADASUM
            # into a tuple-axis allreduce_p).
            return C.hierarchical_allreduce_p(
                buf, op=op, inner_axis=inner, outer_axis=outer,
                prescale_factor=prescale, postscale_factor=postscale)
        if not inv_inner and not inv_outer:
            # Fully varying: one fused all-reduce over both axes.
            return C.allreduce_p(buf, op=op, axis=(inner, outer),
                                 prescale_factor=prescale,
                                 postscale_factor=postscale)
        # Partially/fully invariant: sequential per-axis allreduce_p — each
        # leg handles its own axis's invariance (a tuple-axis psum would
        # re-sum the already-reduced direction).
        return C.allreduce_p(
            C.allreduce_p(buf, op=op, axis=inner,
                          prescale_factor=prescale),
            op=op, axis=outer, postscale_factor=postscale)

    leaves, treedef = jax.tree.flatten(grads)
    if op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE) or len(leaves) <= 1:
        outs = [reduce_buffer(g, C._dp_invariant(g, inner),
                              C._dp_invariant(g, outer)) for g in leaves]
        return jax.tree.unflatten(treedef, outs)

    groups = {}
    for i, leaf in enumerate(leaves):
        key = (str(leaf.dtype), C._dp_invariant(leaf, inner),
               C._dp_invariant(leaf, outer))
        groups.setdefault(key, []).append(i)
    outs = [None] * len(leaves)
    for (_, inv_inner, inv_outer), idxs in groups.items():
        buf = jnp.concatenate([leaves[i].reshape(-1) for i in idxs]) \
            if len(idxs) > 1 else leaves[idxs[0]].reshape(-1)
        red = reduce_buffer(buf, inv_inner, inv_outer)
        off = 0
        for i in idxs:
            size = leaves[i].size
            outs[i] = red[off:off + size].reshape(leaves[i].shape)
            off += size
    return jax.tree.unflatten(treedef, outs)


def _note_state_bytes(state) -> None:
    """Publish the replicated optimizer-state footprint to the native
    ``hvdtpu_optimizer_state_bytes`` gauge (process mode only) — the
    baseline :class:`~.sharded_optimizer.ShardedDistributedOptimizer`'s
    1/world footprint is measured against (docs/optimizer.md)."""
    try:
        from .sharded_optimizer import publish_optimizer_state_bytes
        publish_optimizer_state_bytes(state)
    except Exception:
        pass  # tracing-time init or uninitialized runtime: gauge is best-effort


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters: Any = None,
                         compression=None,
                         backward_passes_per_step: int = 1,
                         op: C.ReduceOp = C.ReduceOp.AVERAGE,
                         gradient_predivide_factor: float = 1.0,
                         prescale_factor: Optional[float] = None,
                         postscale_factor: Optional[float] = None,
                         axis: Optional[str] = None,
                         hierarchical: Optional[Tuple] = None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates use cross-rank-reduced gradients.

    Mirrors ``hvd.DistributedOptimizer`` (reference ``horovod/torch/optimizer.py:383``):

    * ``op``: ``Average`` (default), ``Sum`` or ``Adasum``.
    * ``backward_passes_per_step`` > 1 accumulates that many gradient pytrees
      locally before one fused allreduce + inner update (reference
      ``optimizer.py:67/:104-150``), implemented with ``optax.MultiSteps``.
    * ``gradient_predivide_factor`` splits the averaging between pre- and
      post-reduction scaling (reference ``optimizer.py:383`` factory docs):
      prescale = 1/(size/f), postscale = 1/f.
    * ``compression``: ``hvd.Compression.fp16``/``bf16`` (wire dtype), a
      quantizer (``MaxMinQuantizer``/``NormalizedQuantizer``/``TopKCompressor``)
      or a per-layer :class:`~horovod_tpu.compression.CompressionConfig` —
      quantized gradients route through the compressed reducers. Quantized
      compression engages on *per-rank* gradients (differentiate against
      ``hvd.pvary(params)``); gradients of replicated params arrive pre-summed
      and skip compression. With ``error_feedback=True`` the optimizer state
      carries per-rank residuals — inside a compiled step those are varying
      state and need per-leaf sharded out_specs (or use the eager path).
    * ``named_parameters`` is accepted for signature parity and ignored (optax is
      functional; parameter identity comes from the pytree).
    * ``hierarchical``: ``(inner_axis, outer_axis)`` or ``("auto", inner,
      outer)`` — gradient reduction rides the hierarchical (cross-slice)
      path, as :func:`allreduce_gradients`; reference: the autotuned
      ``NCCLHierarchicalAllreduce`` switch. In-step only; incompatible with
      ``compression``.

    Works inside ``jit``/``shard_map`` (collective lowers to ``lax.psum``) and
    eagerly in either runtime mode.
    """
    if gradient_predivide_factor != 1.0:
        if op != C.ReduceOp.AVERAGE:
            raise ValueError(
                "gradient_predivide_factor not supported with op != Average")
        # Average == prescale 1/size; split it as 1/(size/f) pre, 1/f post
        # (reference: horovod/torch/optimizer.py factory).
        pre = None  # resolved at update time (size may come from the axis)
        post = 1.0 / gradient_predivide_factor
    else:
        pre = prescale_factor
        post = postscale_factor

    # Quantized compression (IST-fork parity) routes through the compressed
    # reducers with per-layer config + optional error feedback; simple wire
    # compressors (fp16/bf16/none) ride the plain allreduce.
    from ..compression import CompressionConfig
    from ..compression.quantize import (MaxMinQuantizer, NormalizedQuantizer,
                                        TopKCompressor)
    quantized = isinstance(compression, (CompressionConfig, MaxMinQuantizer,
                                         NormalizedQuantizer, TopKCompressor))
    comp_cfg = None
    if quantized:
        comp_cfg = compression if isinstance(compression, CompressionConfig) \
            else CompressionConfig(default_compressor=compression)

    if hierarchical is not None and compression is not None:
        raise ValueError(
            "hierarchical gradient reduction does not take a compressor; "
            "use compressed_allreduce over the slow axis instead "
            "(hierarchical_compressed_allreduce_p)")

    def _reduce(grads):
        eff_op = op
        pre_f = 1.0 if pre is None else pre
        post_f = 1.0 if post is None else post
        if gradient_predivide_factor != 1.0:
            if hierarchical is not None:
                # World size spans BOTH mesh axes on the hierarchical path.
                h_inner, h_outer = hierarchical[-2], hierarchical[-1]
                if not C.in_named_trace(h_inner):
                    # Same clear error the predivide==1.0 path gets from
                    # allreduce_gradients, instead of an opaque unbound-
                    # axis failure from size_in_step.
                    raise ValueError(
                        "hierarchical allreduce is in-step only: call "
                        "inside run_step/shard_map over a mesh with both "
                        "axes")
                n = C.size_in_step(h_inner) * C.size_in_step(h_outer)
            else:
                n = C.size_in_step(axis) if C.in_named_trace(axis) \
                    else runtime.size()
            pre_f = gradient_predivide_factor / n
            eff_op = C.ReduceOp.SUM
        if hierarchical is not None:
            return allreduce_gradients(grads, op=eff_op,
                                       prescale_factor=pre_f,
                                       postscale_factor=post_f,
                                       hierarchical=tuple(hierarchical))
        return C.grouped_allreduce(grads, name="grads", op=eff_op,
                                   compression=compression,
                                   prescale_factor=pre_f,
                                   postscale_factor=post_f, axis=axis)

    def _leaf_name(path) -> str:
        import jax.tree_util as jtu
        parts = []
        for k in path:
            if isinstance(k, jtu.DictKey):
                parts.append(str(k.key))
            elif isinstance(k, jtu.SequenceKey):
                parts.append(str(k.idx))
            elif isinstance(k, jtu.GetAttrKey):
                parts.append(str(k.name))
            else:
                parts.append(str(k))
        return "/".join(parts)

    def _compressed_reduce(grads, residuals):
        from ..compression import Compressor
        from ..compression.reducers import compressed_grouped_allreduce
        if op == C.ReduceOp.ADASUM:
            raise ValueError(
                "op=Adasum is not supported with quantized compression "
                "(the compressed reducers are sum-based, like the "
                "reference's); use Adasum without compression")
        flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
        res_leaves = (jax.tree.leaves(residuals) if residuals is not None
                      else [None] * len(flat))
        outs = [None] * len(flat)
        new_res = [None] * len(flat)
        ax = axis if axis is not None else runtime.dp_axis()
        # Same scaling semantics as the dense path (_reduce).
        eff_op = op
        pre_f = 1.0 if pre is None else pre
        post_f = 1.0 if post is None else post
        if gradient_predivide_factor != 1.0:
            n = C.size_in_step(axis) if C.in_named_trace(axis) \
                else runtime.size()
            pre_f = gradient_predivide_factor / n
            post_f = 1.0 / gradient_predivide_factor
            eff_op = C.ReduceOp.SUM

        # Partition leaves: dense / wire-compressed per leaf, quantized leaves
        # grouped by compressor config and FUSED into one buffer per group
        # (reference: CompressionMode::Fused, common.h:164-168 — hundreds of
        # small layers must not pay per-tensor bucket metadata + dispatch).
        groups: dict = {}  # compressor -> list of leaf indices
        for i, ((path, g), r) in enumerate(zip(flat, res_leaves)):
            comp = comp_cfg.for_name(_leaf_name(path))
            if comp is not None and C.in_named_trace(axis) and \
                    C._dp_invariant(g, ax):
                # Invariant gradients are already reduced (autodiff psum for
                # replicated params) — there is nothing to exchange, so
                # quantizing would only add noise. Compression applies to
                # per-rank (varying) gradients: compute them against
                # hvd.pvary(params) to engage the compressed reducers.
                comp = None
            wire_comp = isinstance(comp, type) and issubclass(comp, Compressor)
            if comp is None or wire_comp:
                # Dense (or dtype-cast wire compression): plain allreduce.
                outs[i] = C.allreduce(g, name=f"g/{_leaf_name(path)}",
                                      op=eff_op, prescale_factor=pre_f,
                                      postscale_factor=post_f,
                                      compression=comp, axis=axis)
                new_res[i] = r
            else:
                groups.setdefault(comp, []).append(i)

        for comp, idxs in groups.items():
            g_leaves = [flat[i][1] for i in idxs]
            r_leaves = ([res_leaves[i] for i in idxs]
                        if residuals is not None else None)
            result = compressed_grouped_allreduce(
                tuple(g_leaves), comp, reduction=comp_cfg.reduction,
                op=eff_op, axis=axis, residuals=None if r_leaves is None
                else tuple(r_leaves), prescale_factor=pre_f,
                postscale_factor=post_f)
            if residuals is not None:
                red, nres = result
                for i, o, nr in zip(idxs, red, nres):
                    outs[i], new_res[i] = o, nr
            else:
                for i, o in zip(idxs, result):
                    outs[i] = o

        unflatten = jax.tree_util.tree_unflatten
        grads_out = unflatten(jax.tree.structure(grads), outs)
        res_out = (unflatten(jax.tree.structure(grads), new_res)
                   if residuals is not None else None)
        return grads_out, res_out

    if quantized and comp_cfg.error_feedback:
        # State = (inner optax state, residual pytree) — residuals thread
        # through the compiled step like any optimizer state (reference:
        # feedback_buffer_manager.{h,cc} persistent buffers).
        from ..compression.error_feedback import init_error_feedback

        def init_fn(params):
            state = (optimizer.init(params), init_error_feedback(params))
            _note_state_bytes(state)
            return state

        def update_fn(grads, state, params=None, **extra):
            inner_state, residuals = state
            with jax.named_scope(SCOPE_EXCHANGE):
                reduced, new_residuals = _compressed_reduce(grads, residuals)
            with jax.named_scope(SCOPE_OPTIMIZER):
                updates, inner_state = optimizer.update(
                    reduced, inner_state, params, **extra)
            return updates, (inner_state, new_residuals)
    else:
        def init_fn(params):
            state = optimizer.init(params)
            _note_state_bytes(state)
            return state

        def update_fn(grads, state, params=None, **extra):
            with jax.named_scope(SCOPE_EXCHANGE):
                if quantized:
                    reduced, _ = _compressed_reduce(grads, None)
                else:
                    reduced = _reduce(grads)
            with jax.named_scope(SCOPE_OPTIMIZER):
                return optimizer.update(reduced, state, params, **extra)

    wrapped = optax.GradientTransformation(init_fn, update_fn)
    if backward_passes_per_step > 1:
        return optax.MultiSteps(wrapped,
                                every_k_schedule=backward_passes_per_step)
    return wrapped


def _broadcast_tree(tree, root_rank: int, axis: Optional[str], prefix: str):
    """Broadcast every leaf of a pytree from ``root_rank``.

    Process mode rides the native broadcast (PR 19): the whole tree is
    async-enqueued inside one grouped window — ONE control-plane
    negotiation round and fused execution for same-dtype runs instead of a
    blocking round-trip per leaf — then synchronized. Other modes keep the
    per-leaf dispatch (in-step/SPMD broadcasts are XLA-fused anyway)."""
    leaves, treedef = jax.tree.flatten(tree)
    if (leaves and runtime.mode() == "process"
            and not C.in_named_trace(axis)):
        with C.grouped_enqueue():
            handles = [C.broadcast_async(p, root_rank=root_rank,
                                         name=f"{prefix}.{i}", axis=axis)
                       for i, p in enumerate(leaves)]
        return jax.tree.unflatten(treedef,
                                  [C.synchronize(h) for h in handles])
    return jax.tree.map(
        lambda p: C.broadcast(p, root_rank=root_rank, axis=axis), tree)


def broadcast_parameters(params, root_rank: int = 0,
                         axis: Optional[str] = None):
    """Broadcast a parameter pytree from ``root_rank`` to all ranks
    (reference: ``horovod/torch/functions.py:30``)."""
    return _broadcast_tree(params, root_rank, axis, "broadcast_parameters")


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              axis: Optional[str] = None):
    """Broadcast optimizer state from ``root_rank``
    (reference: ``horovod/torch/functions.py:62``). With optax, state is a pytree
    — same mechanism as parameters (the reference needs torch-specific walking)."""
    return _broadcast_tree(opt_state, root_rank, axis,
                           "broadcast_optimizer_state")


class DistributedGradientTape:
    """Callable-style parity shim for TF's ``DistributedGradientTape``
    (reference ``horovod/tensorflow/__init__.py:527``): wraps a ``jax.grad``-style
    function so returned gradients are allreduced."""

    def __init__(self, grad_fn, op: C.ReduceOp = C.ReduceOp.AVERAGE,
                 compression=None, axis: Optional[str] = None,
                 hierarchical: Optional[Tuple] = None):
        self._grad_fn = grad_fn
        self._op = op
        self._compression = compression
        self._axis = axis
        self._hierarchical = hierarchical

    def __call__(self, *args, **kwargs):
        out = self._grad_fn(*args, **kwargs)
        if isinstance(out, tuple) and len(out) == 2:
            # value_and_grad convention: (value, grads)
            value, grads = out
            return value, allreduce_gradients(
                grads, op=self._op, compression=self._compression,
                axis=self._axis, hierarchical=self._hierarchical)
        return allreduce_gradients(out, op=self._op,
                                   compression=self._compression,
                                   axis=self._axis,
                                   hierarchical=self._hierarchical)
