"""Shared Pallas helpers (no deps — importable from any kernel module)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def use_interpret() -> bool:
    """Whether a kernel runs in Pallas interpret mode: the same gate as the
    quantize kernels, compiled through Mosaic on the TPU backend only;
    everything else (the CPU-mesh tests) runs the interpreter."""
    from ..compression.quantize import _pallas_backend_enabled
    return not _pallas_backend_enabled(None)


def div(x, n: int):
    """Grid indices are int32 and not negative: lax's truncating division
    with the divisor in the index's dtype (a Python int would be int64
    under ``jax_enable_x64``, which the tests set)."""
    return jax.lax.div(x, jnp.asarray(n, x.dtype))


def rem(x, n: int):
    return jax.lax.rem(x, jnp.asarray(n, x.dtype))
