"""horovod_tpu — TPU-native distributed deep-learning training framework.

A ground-up rebuild of the capability surface of Horovod 0.20 + the IST-DASLab
gradient-compression fork (reference: ``/root/reference``), designed for TPUs:
collectives are XLA programs over ICI/DCN (JAX ``shard_map``/``pjit``), compression
kernels are Pallas, and the eager multi-process runtime is a native C++ controller
with rank-0 negotiation, tensor fusion and ring reduction over TCP — no MPI/NCCL.

Public surface mirrors ``import horovod.torch as hvd`` (reference
``horovod/torch/__init__.py``) plus TPU-first additions (mesh/step helpers,
reducescatter, sequence/context parallel primitives).
"""

__version__ = "0.1.0"

# Topology / lifecycle (reference: horovod/common/basics.py).
from .runtime import (init, shutdown, is_initialized, rank, size, local_rank,
                      local_size, cross_rank, cross_size, is_homogeneous, mesh,
                      dp_axis, mode, start_timeline, stop_timeline,
                      start_trace, stop_trace,
                      metrics, metrics_dump, debugz, flightrec_dump,
                      perf_report, grad_report, profile, prof_start,
                      prof_stop, prof_snapshot)

# Collectives (reference: horovod/torch/mpi_ops.py).
from .ops.collectives import (
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
    allreduce, allreduce_async, grouped_allreduce, grouped_enqueue,
    allgather, allgather_async, broadcast, broadcast_async,
    alltoall, alltoall_async, reducescatter, join, poll, synchronize,
    release_handle, hierarchical_allreduce_p, hierarchical_allgather_p,
    # In-step primitives (inside shard_map / run_step).
    allreduce_p, allgather_p, broadcast_p, alltoall_p, reducescatter_p,
    ppermute_p, rank_in_step, size_in_step, in_named_trace, pvary,
)

# Optimizer / gradient API (reference: horovod/torch/optimizer.py,
# horovod/tensorflow/__init__.py DistributedGradientTape).
from .parallel.optimizer import (DistributedOptimizer, DistributedGradientTape,
                                 allreduce_gradients, broadcast_parameters,
                                 broadcast_optimizer_state)
# ZeRO-style cross-replica sharded weight update (arXiv:2004.13336;
# TPU-first extension, no reference analog).
from .parallel.sharded_optimizer import ShardedDistributedOptimizer

# Flat-vs-hierarchical calibration (reference: the parameter manager's
# categorical hierarchical_allreduce switch, parameter_manager.h:186).
from .parallel.strategy import (autotune_hierarchical, choose_hierarchical,
                                clear_hierarchical_decisions,
                                load_hierarchical_decisions,
                                save_hierarchical_decisions)

# Sequence/context parallelism (TPU-first; no reference analog — SURVEY.md §2.7).
from .parallel.ring_attention import ring_attention, ring_attention_p
from .parallel.ulysses import ulysses_attention, ulysses_attention_p
# Fused (flash) causal attention Pallas kernel (TPU-first extension).
from .ops.flash_attention import flash_attention

# Compression (reference: horovod/torch/compression.py + IST fork subsystem).
from .compression import Compression, set_quantization_levels

# Object collectives (reference: horovod/torch/functions.py).
from .functions import broadcast_object, allgather_object

# Sharded checkpointing (orbax-backed; TPU-first — the reference leaves
# checkpoint format to the user framework, SURVEY.md §5).
from .checkpoint import (save_checkpoint, restore_checkpoint,
                         latest_checkpoint_step, checkpoint_metadata)

# Compiled-step helpers (TPU-native).
from .step import (run_step, data_parallel_step, shard_batch, replicate,
                   batch_spec, compiled_step_report, REPLICATED)

from .exceptions import (HvdTpuInternalError, HostsUpdatedInterrupt,
                         TensorShapeMismatchError, TensorDtypeMismatchError,
                         DuplicateNameError, NotInitializedError)

from .callbacks import (average_metrics, warmup_schedule,  # noqa: E402
                        lr_schedule, BestModelCheckpoint)
from . import elastic  # noqa: E402  (reference: horovod/torch/elastic.py)


def __getattr__(name):
    # SyncBatchNorm is the only top-level symbol needing flax; load lazily so
    # `import horovod_tpu` works in flax-less environments.
    if name == "SyncBatchNorm":
        from .parallel.sync_batch_norm import SyncBatchNorm
        return SyncBatchNorm
    raise AttributeError(f"module 'horovod_tpu' has no attribute {name!r}")


def mpi_threads_supported() -> bool:
    """Signature parity with ``hvd.mpi_threads_supported()``
    (reference ``basics.py``): there is no MPI here; returns False."""
    return False


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    """The native TCP controller fills gloo's role (process mode)."""
    from . import runtime as _rt
    return _rt.is_initialized() and _rt.mode() == "process"


def gloo_built() -> bool:
    return True


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False
