"""What the Pallas kernel families share, said once: the tile's widths, the
masked exponent, the two contraction patterns, the platform test and a few
helpers. The floor of ``ops/``: it imports nothing of the package, and every
kernel module (``flash_attention``, ``ssd``, ``s6``, ``gated_delta``,
``conv``, ``cca``) imports these names from here and no kernel's part from another
(``cca`` takes two plain references, ``conv.causal_conv1d`` and
``attention.rope``, for the lines its kernels are held to)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128      # the TPU's lane width: a tile's minor axis
SUBLANES = 16    # rows of a bfloat16 tile
NEG_INF = -1e30  # the masked exponent: exp gives 0, and no inf - inf
NT = (((1,), (1,)), ((), ()))  # a · bᵀ: contract the last dim of both
TN = (((0,), (0,)), ((), ()))  # aᵀ · b: contract the first dim of both


def out_vma(*args) -> frozenset:
    """Union of the inputs' varying-mesh-axes sets, for ``pallas_call``
    out-shape annotation. A pallas_call inside a ``check_vma=True``
    shard_map (the compressed reducers' collective programs; the flash
    kernel as Ulysses' inner attention) must declare how its outputs vary
    across mesh axes — and a per-shard kernel's outputs vary exactly as
    its inputs do. Empty (a no-op) outside shard_map."""
    vma = frozenset()
    for a in args:
        vma |= getattr(jax.typeof(a), "vma", frozenset())
    return vma


def on_tpu() -> bool:
    """The package's one platform test: a Pallas kernel compiles through
    Mosaic on the TPU backend and has no lowering for another. (A compile for
    a described chip in the sandbox, where the backend is the CPU, replaces
    this function: ``scripts/aot_step.py``,
    ``tests/test_flash_mosaic_compile.py``.)"""
    return jax.default_backend() == "tpu"


def use_interpret() -> bool:
    """Whether a kernel runs in Pallas interpret mode: everywhere but on the
    TPU (the CPU-mesh tests)."""
    return not on_tpu()


def largest_divisor(n: int, most: int) -> int:
    """The largest divisor of ``n``, ``most`` at most."""
    return next(d for d in range(min(most, n), 0, -1) if n % d == 0)


def varying_like(x, like):
    """``x`` marked as varying over the mesh axes ``like`` varies over:
    inside a ``shard_map`` a scan's carry must enter with the type it leaves
    with, and a ``custom_vjp``'s cotangent come back with its input's (the
    mark's own transpose sums a replicated parameter's over the ranks)."""
    axes = jax.typeof(like).vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(axes), to="varying") if axes else x


def always(body, axis: int = 2):
    """Run ``body`` under a predicate that always holds (of the grid's
    ``axis``), NOT unguarded:
    interpret mode inside a ``shard_map`` matches the varying axes of a
    block's fetch only along a ``pl.when`` path (an unguarded body trips
    "dynamic_slice requires varying manual axes to match"); compiled, Mosaic
    folds the constant."""
    pl.when(pl.program_id(axis) >= 0)(body)


def div(x, n: int):
    """Grid indices are int32 and not negative: lax's truncating division
    with the divisor in the index's dtype (a Python int would be int64
    under ``jax_enable_x64``, which the tests set)."""
    return jax.lax.div(x, jnp.asarray(n, x.dtype))


def rem(x, n: int):
    return jax.lax.rem(x, jnp.asarray(n, x.dtype))
