"""Compressed-vs-dense allreduce A/B: wire model + live calibration.

Reference: the fork ships ``HOROVOD_NCCL_FAKE_COMPRESSION``
(``horovod/common/ops/compressed/nccl_compressed_operations.h``, the A/B
knob cited at ``nccl_operations.h:87-89``) so users can measure the
*performance* effect of compression independently of its numerics. This
module is the TPU analog: it answers "would the compressed DCN hop beat the
dense one on MY fabric?" without changing what the training step computes.

Two layers:

* A closed-form **ring-allreduce wire model** (:func:`projected_step_seconds`,
  :func:`crossover_gbps`): dense moves ``2 * nbytes`` per link direction,
  compressed moves ``2 * comp_bytes`` plus the quantize/dequantize compute.
  Compression wins exactly below the crossover link speed — the fork's
  raison d'être (its published wins are on 25 Gb/s RoCE; ICI at ~100+ GB/s
  correctly favors dense).

* A live **A/B calibration** (:func:`autotune_compressed`) that times the
  real dense-hierarchical vs compressed-hierarchical programs on the mesh,
  mirroring :func:`~horovod_tpu.parallel.strategy.autotune_hierarchical`
  (injectable ``measure`` for bandwidth-model tests; coordinator-synced
  results). Unlike ``hierarchical="auto"`` this is ADVISORY ONLY: switching
  to compression changes the numbers a step produces (lossy quantization +
  error feedback), so it must never be flipped on by a timing near-tie —
  the user reads the table and opts in via
  :class:`~horovod_tpu.compression.config.CompressionConfig`, exactly as
  reference users opt in via ``HOROVOD_COMPRESSION`` env knobs.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import runtime
from ..ops import collectives as C
from ..utils import logging as log


def payload_nbytes(compressor, nelem: int, dtype=jnp.float32) -> int:
    """Wire bytes of ``compressor``'s payload for an ``nelem`` buffer,
    computed from traced shapes alone (``jax.eval_shape`` — no device
    execution), including per-bucket metadata leaves."""
    spec = jax.ShapeDtypeStruct((int(nelem),), dtype)
    shapes = jax.eval_shape(lambda v: compressor.compress(v)[0], spec)
    return int(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(shapes)))


def projected_step_seconds(nbytes: int, comp_bytes: int, compute_s: float,
                           gbps: float) -> Tuple[float, float]:
    """(dense_s, compressed_s) for one ring allreduce across a ``gbps`` link:
    wire time is ``2 * bytes / bw`` (reduce + gather directions); the
    compressed variant adds its quantize/dequantize compute."""
    bw = gbps * 1e9 / 8.0
    return 2.0 * nbytes / bw, 2.0 * comp_bytes / bw + compute_s


def crossover_gbps(nbytes: int, comp_bytes: int,
                   compute_s: float) -> Optional[float]:
    """Link speed below which compression wins: the wire-byte savings
    (both ring directions) paid out at ``bw`` equal the compression compute
    at exactly this speed. ``None`` when compression can NEVER win (no byte
    savings); ``inf`` when it ALWAYS wins (savings at zero compute cost) —
    distinct sentinels, since a caller reading None as "never pays" for the
    free-compute case would conclude the opposite of the truth."""
    saved_bytes = 2.0 * (nbytes - comp_bytes)
    if saved_bytes <= 0:
        return None
    if compute_s <= 0:
        return float("inf")
    return saved_bytes * 8.0 / compute_s / 1e9


def _variant_fn(kind: str, inner_axis: str, outer_axis: str, compressor):
    """Jitted dense-hierarchical or compressed-hierarchical allreduce over
    the live mesh (pvary first — a replicated input would short-circuit the
    collectives and time a no-op, same hazard as strategy._variant_fn)."""
    from .reducers import hierarchical_compressed_allreduce_p

    mesh = runtime.mesh()

    if kind == "dense":
        def body(s):
            s = C.pvary(C.pvary(s, inner_axis), outer_axis)
            return C.hierarchical_allreduce_p(s, op=C.ReduceOp.SUM,
                                              inner_axis=inner_axis,
                                              outer_axis=outer_axis)
    else:
        def body(s):
            s = C.pvary(C.pvary(s, inner_axis), outer_axis)
            return hierarchical_compressed_allreduce_p(
                s, compressor, inner_axis=inner_axis,
                outer_axis=outer_axis, op=C.ReduceOp.SUM)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=P()))


def _default_measure(kind: str, nbytes: int, inner_axis: str,
                     outer_axis: str, reps: int, compressor) -> float:
    nelem = max(nbytes // 4, 1)
    x = jnp.ones((nelem,), jnp.float32)
    fn = _variant_fn(kind, inner_axis, outer_axis, compressor)
    jax.block_until_ready(fn(x))  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def autotune_compressed(inner_axis: str, outer_axis: str,
                        sizes: Tuple[int, ...] = (1 << 20, 16 << 20),
                        reps: int = 5, compressor=None,
                        measure: Optional[Callable] = None
                        ) -> Dict[int, Tuple[str, float, float]]:
    """Time dense-hierarchical vs compressed-hierarchical allreduce at each
    message size on the live mesh; returns
    ``{nbytes: ("dense"|"compressed", dense_s, compressed_s)}``.

    ``measure(kind, nbytes, inner_axis, outer_axis, reps) -> seconds`` with
    ``kind in ("dense", "compressed")`` is injectable for bandwidth-model
    tests, exactly like ``autotune_hierarchical``'s hook. Default
    ``compressor``: 4-bit :class:`~horovod_tpu.compression.MaxMinQuantizer`.

    Multi-host: process 0's timings are broadcast before winners are
    computed, so every process logs the identical table (the numbers feed a
    HUMAN decision, but divergent logs across hosts would still mislead).

    ADVISORY: the result is never consulted by ``allreduce_gradients`` —
    compression changes step numerics, so opting in stays explicit (see
    module docstring).
    """
    if compressor is None:
        from .quantize import MaxMinQuantizer
        compressor = MaxMinQuantizer(bits=4)
    if measure is None:
        def measure(kind, nbytes, ia, oa, reps):
            return _default_measure(kind, nbytes, ia, oa, reps, compressor)
    sizes_sorted = sorted(sizes)
    times = np.array(
        [[measure("dense", nb, inner_axis, outer_axis, reps),
          measure("compressed", nb, inner_axis, outer_axis, reps)]
         for nb in sizes_sorted], np.float64)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        times = np.asarray(multihost_utils.broadcast_one_to_all(times))
    results: Dict[int, Tuple[str, float, float]] = {}
    for (dense_s, comp_s), nbytes in zip(times, sizes_sorted):
        dense_s, comp_s = float(dense_s), float(comp_s)
        winner = "compressed" if comp_s < dense_s else "dense"
        results[nbytes] = (winner, dense_s, comp_s)
        log.info(f"autotune_compressed[{inner_axis},{outer_axis}] "
                 f"{nbytes >> 20}MB: dense={dense_s * 1e3:.3f}ms "
                 f"compressed={comp_s * 1e3:.3f}ms -> {winner}")
    return results
