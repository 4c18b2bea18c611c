"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

No reference analog (SURVEY.md §2.7: sequence parallelism ABSENT; the enabling
primitive the reference does ship is ``alltoall``, ``operations.cc:1055-1116``,
which is exactly what this composes). DeepSpeed-Ulysses pattern, TPU-native:
Q/K/V arrive sequence-sharded ``[B, S/n, H, D]``; one ``lax.all_to_all`` per
tensor re-shards to head-sharded ``[B, S, H/n, D]`` so every device runs *full-
sequence* attention over its head subset; a final all-to-all restores sequence
sharding. Two ICI all-to-alls total, and any inner attention function works
unchanged (full sequence is materialized per device) — complementary to
:mod:`ring_attention`, which never materializes the full sequence.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
from jax import lax

from .. import runtime
from ..ops import collectives as C
from ..ops.attention import repeat_kv_heads as _repeat_kv_heads
from ..ops.flash_attention import flash_attention
from .ring_attention import _require_axis


def _heads_first(x, ax: str):
    """[B, S/n, H, D] -> [B, S, H/n, D]: scatter heads, gather sequence."""
    return lax.all_to_all(x, ax, split_axis=2, concat_axis=1, tiled=True)


def _seq_first(x, ax: str):
    """[B, S, H/n, D] -> [B, S/n, H, D]: scatter sequence, gather heads."""
    return lax.all_to_all(x, ax, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_p(q, k, v, causal: bool = True,
                        axis: Optional[str] = None,
                        attn_fn: Callable = flash_attention):
    """In-step Ulysses attention over mesh axis ``axis``.

    Args:
      q, k, v: ``[B, S_shard, H, D]`` sequence-sharded blocks; ``H`` must be
        divisible by the mesh-axis size (heads are scattered across it).
      attn_fn: inner full-sequence attention, signature
        ``(q, k, v, causal=...)``; default the flash kernel
        (:func:`horovod_tpu.ops.flash_attention.flash_attention`): each
        device holds the whole sequence, so a dense inner would hold its
        S x S logits. Tests pass the dense reference here.
    """
    ax = _require_axis(axis, "ulysses_attention_p")
    n = lax.axis_size(ax)
    if q.shape[2] % n:
        raise ValueError(
            f"Ulysses needs heads ({q.shape[2]}) divisible by the "
            f"'{ax}' axis size ({n}); use ring_attention otherwise")
    # GQA: repeat K/V heads up to the query head count *before* the exchange so
    # the head scatter keeps query head i aligned with its kv group (jnp.repeat
    # is a block repeat, matching head i -> kv head i // group). Costs alltoall
    # bytes; ring_attention circulates compact heads if that matters.
    k = _repeat_kv_heads(k, q.shape[2])
    v = _repeat_kv_heads(v, q.shape[2])
    qh, kh, vh = (_heads_first(t, ax) for t in (q, k, v))
    out = attn_fn(qh, kh, vh, causal=causal)
    return _seq_first(out, ax)


def ulysses_attention(q, k, v, causal: bool = True, axis: Optional[str] = None,
                      attn_fn: Callable = flash_attention):
    """Ulysses attention, in-step or eager (shard_maps itself when the mesh
    axis is not bound — mirrors :func:`ring_attention`)."""
    ax = _require_axis(axis, "ulysses_attention")
    if C.in_named_trace(ax):
        return ulysses_attention_p(q, k, v, causal=causal, axis=ax,
                                   attn_fn=attn_fn)
    from jax.sharding import PartitionSpec as P
    mesh = runtime.mesh()
    seq_spec = P(None, ax)
    mapped = jax.shard_map(
        lambda q, k, v: ulysses_attention_p(q, k, v, causal=causal, axis=ax,
                                            attn_fn=attn_fn),
        mesh=mesh, in_specs=(seq_spec,) * 3, out_specs=seq_spec)
    return mapped(q, k, v)
