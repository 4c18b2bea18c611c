"""Ring attention: exact context-parallel attention over a mesh axis.

No reference analog — Horovod has no sequence/context parallelism (SURVEY.md
§2.7: "TP / PP / SP / EP / CP / ring-attention: ABSENT"); the closest primitive
is ``alltoall``. This module is the TPU-first long-context mechanism the rebuild
makes first-class: sequence-sharded Q/K/V blocks circulate around the mesh axis
via ``lax.ppermute`` (one ICI hop per step, overlapping compute with the
neighbor exchange), accumulating exact softmax attention with the
flash-attention online-softmax recurrence (fp32 accumulators). Differentiable —
the transpose of ``ppermute`` is the reverse permute, so autodiff yields the
ring-attention backward pass for free.

Layout: ``q``/``k``/``v`` are ``[batch, seq_shard, heads, head_dim]`` with the
sequence dimension sharded contiguously over the mesh axis (shard *r* holds
global positions ``r*S .. (r+1)*S-1``); pass ``q_positions``/``kv_positions``
for any other layout.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import runtime
from ..ops import collectives as C
# ops is the lower layer, so parallel imports from it (and never from models).
from ..ops.attention import repeat_kv_heads as _repeat_kv_heads

SP_AXIS = "sp"

_NEG_INF = float(np.finfo(np.float32).min)


def _default_axis(axis: Optional[str]) -> Optional[str]:
    """The context-parallel mesh axis: explicit, else the mesh's "sp" axis.

    Deliberately NOT the data-parallel axis: ringing over dp would attend
    queries against other ranks' K/V from different *batch* elements and
    silently produce garbage. Returns None when no axis applies (the
    caller raises)."""
    if axis is not None:
        return axis
    try:
        if SP_AXIS in runtime.axis_names():
            return SP_AXIS
    except Exception:
        pass
    return None


def _require_axis(axis: Optional[str], who: str) -> str:
    ax = _default_axis(axis)
    if ax is None:
        raise ValueError(
            f"{who}: no sequence-parallel mesh axis — pass axis= explicitly "
            f"or init() with a mesh containing an '{SP_AXIS}' axis")
    return ax


def ring_attention_p(q, k, v, causal: bool = True,
                     axis: Optional[str] = None,
                     q_positions=None, kv_positions=None):
    """In-step (inside shard_map) ring attention over mesh axis ``axis``.

    Args:
      q: ``[B, Sq_shard, H, D]`` query block (this rank's sequence shard).
      k, v: ``[B, Sk_shard, Hkv, D]`` key/value blocks; ``Hkv`` may divide ``H``
        (GQA), and ``v`` may be ``Dv`` wide where ``q`` and ``k`` are ``D``
        (latent attention): the output is then ``Dv`` wide.
      causal: apply causal masking using global positions.
      axis: mesh axis name to ring over (default: the mesh's "sp" axis; raises
        if the mesh has none — there is deliberately no dp fallback, see
        :func:`_default_axis`).
      q_positions / kv_positions: optional ``[Sq_shard]`` / ``[Sk_shard]``
        global position vectors; default assumes contiguous sharding.

    Returns ``[B, Sq_shard, H, D]`` — exact attention output for this shard.
    """
    ax = _require_axis(axis, "ring_attention_p")
    n = lax.axis_size(ax)
    idx = lax.axis_index(ax)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if H % k.shape[2]:
        raise ValueError(
            f"query heads ({H}) not a multiple of kv heads ({k.shape[2]})")

    if q_positions is None:
        q_positions = idx * Sq + jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = idx * Sk + jnp.arange(Sk)

    scale = 1.0 / np.sqrt(D)
    q32 = q.astype(jnp.float32) * scale

    # Online-softmax accumulators (flash recurrence), [B, H, Sq] layout.
    o_acc = jnp.zeros((B, H, Sq, v.shape[-1]), jnp.float32)
    l_acc = jnp.zeros((B, H, Sq), jnp.float32)
    m_acc = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    # n is static under shard_map, so this Python loop unrolls into n
    # ppermute+matmul pairs that XLA overlaps (compute on block t while
    # block t+1 is in flight on ICI). GQA: the compact Hkv-head k/v are what
    # circulates on ICI; the head repeat happens locally at matmul time.
    for t in range(n):
        kr = _repeat_kv_heads(k, H).astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, kr)
        if causal:
            mask = q_positions[:, None] >= kv_positions[None, :]  # [Sq, Sk]
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        blk_max = jnp.max(logits, axis=-1)                         # [B, H, Sq]
        new_m = jnp.maximum(m_acc, blk_max)
        # Fully-masked-so-far rows have m == -inf; keep exp() NaN-free.
        safe_m = jnp.where(new_m <= _NEG_INF, 0.0, new_m)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(logits <= _NEG_INF, 0.0, p)
        corr = jnp.where(m_acc <= _NEG_INF, 0.0, jnp.exp(m_acc - safe_m))
        l_acc = l_acc * corr + jnp.sum(p, axis=-1)
        vr = _repeat_kv_heads(v, H).astype(jnp.float32)
        o_acc = (o_acc * corr[..., None] +
                 jnp.einsum("bhqk,bkhd->bhqd", p, vr))
        m_acc = new_m
        if t != n - 1:
            k, v, kv_positions = lax.ppermute(
                (k, v, kv_positions), ax, perm=perm)

    denom = jnp.where(l_acc == 0.0, 1.0, l_acc)
    out = o_acc / denom[..., None]                                  # [B,H,Sq,D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention(q, k, v, causal: bool = True, axis: Optional[str] = None,
                   q_positions=None, kv_positions=None):
    """Ring attention, usable inside *or* outside a shard-mapped step.

    Inside ``run_step``/``shard_map`` (the mesh axis is bound) this is
    :func:`ring_attention_p`. Outside, it shard_maps itself over the runtime
    mesh with the sequence dimension sharded on ``axis``.
    """
    ax = _require_axis(axis, "ring_attention")
    if C.in_named_trace(ax):
        return ring_attention_p(q, k, v, causal=causal, axis=ax,
                                q_positions=q_positions,
                                kv_positions=kv_positions)
    from jax.sharding import PartitionSpec as P
    mesh = runtime.mesh()
    # Global sequence length is known here, so default positions materialize
    # outside the shard_map and arrive pre-sliced per shard.
    if q_positions is None:
        q_positions = jnp.arange(q.shape[1])
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1])
    seq_spec = P(None, ax)
    mapped = jax.shard_map(
        lambda q, k, v, qp, kp: ring_attention_p(
            q, k, v, causal=causal, axis=ax, q_positions=qp, kv_positions=kp),
        mesh=mesh, in_specs=(seq_spec,) * 3 + (P(ax),) * 2,
        out_specs=seq_spec)
    return mapped(q, k, v, q_positions, kv_positions)
