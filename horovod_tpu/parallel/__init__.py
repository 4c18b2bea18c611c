"""Parallelism strategies: data-parallel optimizer, Adasum, and the TPU-first
sequence/context-parallel primitives (ring attention, Ulysses)."""

from .optimizer import (DistributedOptimizer, DistributedGradientTape,  # noqa: F401
                        allreduce_gradients, broadcast_parameters,
                        broadcast_optimizer_state)
from ..ops.adasum import adasum_p, adasum_reference  # noqa: F401
from .sharded_optimizer import ShardedDistributedOptimizer  # noqa: F401
from .ring_attention import ring_attention, ring_attention_p  # noqa: F401
from .ulysses import ulysses_attention, ulysses_attention_p  # noqa: F401
