"""What every attention path shares: the dense reference, the rotary
embedding and grouped-query head tiling. Pure ``jax.numpy``.

The flash kernels (:mod:`horovod_tpu.ops.flash_attention`), ring attention
and Ulysses (:mod:`horovod_tpu.parallel`) and the GPT block
(:mod:`horovod_tpu.models.gpt`) import from here, and the tests hold each
of them to :func:`default_attention`. A new attention kind (a window, a
segment mask) starts with its reference in this file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def block_diffusion_mask(rows: int, block: int):
    """The ``[rows, rows]`` boolean mask of training by diffusion over
    blocks (BD3-LM, arXiv:2503.09573) on a sequence of two halves, the
    noised copy then the clean copy of ``rows / 2`` positions in blocks of
    ``block``: a noised query sees its own noised block, both ways, and the
    clean blocks before its own; a clean query sees the clean blocks up to
    its own; no clean query sees a noised key."""
    half = rows // 2
    if rows % 2 or block < 1 or half % block:
        raise ValueError(f"block_diffusion={block} wants a sequence of two "
                         f"halves of whole blocks, got {rows} rows")
    at = np.arange(rows)
    noised, blk = at < half, at % half // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return (q_noised & k_noised & (k_blk == q_blk)) \
        | (q_noised & ~k_noised & (k_blk < q_blk)) \
        | (~q_noised & ~k_noised & (k_blk <= q_blk))


def default_attention(q, k, v, causal: bool = True, window=None,
                      block_diffusion=None):
    """Plain softmax attention. q/k/v: [B, S, H, D]. Computed in fp32 softmax.
    ``window`` (causal only): a query sees itself and the ``window - 1`` keys
    before it, ``0 <= i - j < window``. ``block_diffusion`` (causal only, no
    window beside it): the sequence is a noised and a clean copy of ``S / 2``
    positions under :func:`block_diffusion_mask` at that block length.

    Materializes the ``[B, H, S, S]`` float32 logits: the reference the other
    paths are tested against, not a path to train long sequences on."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if window is not None and not causal:
        raise ValueError("a window is a causal band")
    if block_diffusion is not None:
        if window is not None or not causal \
                or logits.shape[-2] != logits.shape[-1]:
            raise ValueError("block_diffusion masks a sequence against "
                             "itself, beside neither a window nor "
                             "causal=False")
        logits = jnp.where(
            block_diffusion_mask(logits.shape[-1], block_diffusion), logits,
            jnp.finfo(jnp.float32).min)
    elif causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), klen - qlen)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((qlen, klen), dtype=bool),
                                    klen - qlen - window)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def rope(x, positions, base: float = 10000.0, rotary_dim=None):
    """Rotary position embedding. x: [B, S, H, D]; positions: [B, S].
    ``rotary_dim`` (None: all of ``D``) rotates the first ``rotary_dim``
    dimensions of a head, the two halves of those against each other, and
    leaves the rest as they are."""
    d = x.shape[-1]
    if rotary_dim is not None and rotary_dim != d:
        if not 0 < rotary_dim < d or rotary_dim % 2:
            raise ValueError(f"rotary_dim must be even and at most the "
                             f"head's {d}, got {rotary_dim}")
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, base), x[..., rotary_dim:]],
            axis=-1)
    half = d // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def repeat_kv_heads(k, n_q_heads: int):
    """Grouped-query attention: tile K/V heads up to the query head count
    (the compact heads are what cross the wire; the repeat is local).
    Shared by ring, Ulysses and dense attention; the flash kernels read
    K/V at their own head count instead."""
    n_kv = k.shape[2]
    if n_kv == n_q_heads:
        return k
    if n_q_heads % n_kv:
        raise ValueError(
            f"query heads ({n_q_heads}) not a multiple of kv heads ({n_kv})")
    return jnp.repeat(k, n_q_heads // n_kv, axis=2)
