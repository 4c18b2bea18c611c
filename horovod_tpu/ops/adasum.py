"""Adasum adaptive-summation reduction.

Reference: ``horovod/common/ops/adasum/adasum.h:38`` — recursive pairwise exchange
where each pair combines gradients ``a``, ``b`` as::

    a_coeff = 1 - dot(a, b) / (2 * |a|^2)      (1 if |a|^2 == 0)
    b_coeff = 1 - dot(a, b) / (2 * |b|^2)      (1 if |b|^2 == 0)
    result  = a_coeff * a + b_coeff * b

so orthogonal gradients add and parallel gradients average — scale-invariant mixing
of learning contributions (see docs/adasum_user_guide.rst and the fused dot/norm
kernels at ``adasum.h:101-117``).

TPU-native redesign: the reference does vector-halving distance-doubling over MPI
point-to-points. Here the pairwise exchange is a hypercube of ``lax.ppermute`` steps
inside the compiled program — XLA schedules the ICI sends — with the same combine
math, validated against the NumPy model below (mirroring
``test/test_adasum_pytorch.py``'s strategy).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src.lax.parallel import all_gather_invariant


def _combine(a, b, dot, na2, nb2):
    one = jnp.float32(1.0)
    a_coeff = jnp.where(na2 == 0, one, 1.0 - dot / (2.0 * jnp.where(na2 == 0, 1.0, na2)))
    b_coeff = jnp.where(nb2 == 0, one, 1.0 - dot / (2.0 * jnp.where(nb2 == 0, 1.0, nb2)))
    return a_coeff * a + b_coeff * b


def adasum_p(x, axis: str):
    """In-step Adasum over mesh axis ``axis`` (use inside shard_map).

    Vector-halving distance-doubling, like the reference's VHDD
    (``adasum.h:168`` FusedAllreduce): at level L each pair ``(r, r^L)``
    exchanges only the half-segment the other keeps, so the whole
    reduce-scatter phase moves ~1x the vector per rank (the round-1
    implementation moved the full vector every hop). The Adasum coefficients
    need *global* dot/norms of the two logical vectors being combined — each
    rank holds only a piece, so per-piece partials are summed over the
    2L-sized exchange group (reference: ``FusedPairwiseReduceWithComm``'s
    ``SumAllreduceWithComm`` over ``reduction_comms[comm_index]``), here via
    one tiny 3-scalar all_gather per level. Reassembly is one all_gather of
    the combined segments: the reduce-scatter halves the vector MSB-first,
    so hypercube rank ``j``'s segment sits at the STATIC offset
    ``length * bitrev(j) / p`` — reconstruction is a compile-time
    concatenation of the gathered rows in bit-reversed order, no further
    reduction. The final hop therefore moves ~1x the vector per rank
    (allgather-optimal; a masked psum would lower to a full-vector
    all-reduce, ~2x the bytes).
    ``test_adasum.py::test_reassembly_lowers_to_allgather`` pins the
    lowering.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    orig_dtype = x.dtype
    orig_shape = x.shape
    v = x.astype(jnp.float32).reshape(-1)

    # Fold ranks beyond the largest power of two into their partner by plain
    # addition (reference handles non-power-of-two the same way before the
    # recursive exchange).
    p = 1
    while p * 2 <= n:
        p *= 2
    r = n - p
    if r > 0:
        perm_down = [(p + i, i) for i in range(r)]
        incoming = lax.ppermute(v, axis, perm=perm_down)
        v = jnp.where(idx < r, v + incoming, v)

    # Pad so the segment halves evenly at every level.
    count = v.shape[0]
    pad = (-count) % p
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    length = v.shape[0]

    # Reduce-scatter phase: segment halves at each level. At level 2^k a
    # member with bit k set keeps the upper half, adding length / 2^(k+1) to
    # its segment's offset — so member j's final offset is
    # length * bitrev(j) / p (MSB-first halving = bit-reversal placement),
    # static per member and recoverable at reassembly without any index
    # bookkeeping on the wire.
    seg = v
    seg_size = length
    level = 1
    while level < p:
        half = seg_size // 2
        upper = (idx & level) != 0
        keep = jnp.where(upper, seg[half:], seg[:half])
        send = jnp.where(upper, seg[:half], seg[half:])
        perm = [(i, i ^ level) for i in range(p)]
        other = lax.ppermute(send, axis, perm=perm)
        # 'a' is the lower-side logical vector's piece, 'b' the upper side's.
        a = jnp.where(upper, other, keep)
        b = jnp.where(upper, keep, other)
        partial = jnp.stack([jnp.sum(a * b), jnp.sum(a * a), jnp.sum(b * b)])
        gathered = lax.all_gather(partial, axis)  # [n, 3] — 3 scalars/rank
        group = (jnp.arange(n) // (2 * level)) == (idx // (2 * level))
        dot, na2, nb2 = jnp.sum(
            jnp.where(group[:, None], gathered, 0.0), axis=0)
        combined = _combine(a, b, dot, na2, nb2)
        seg = jnp.where(idx < p, combined, seg[:half])
        seg_size = half
        level *= 2

    # Reassemble with one provably-replicated all-gather (allgather-optimal:
    # ~1x the vector per rank): gather every member's combined segment and
    # concatenate rows in bit-reversed member order — segment position m
    # belongs to hypercube rank bitrev(m) (bit reversal is an involution).
    # Extra (non-power-of-two) ranks contribute ignored rows and receive the
    # replicated result like everyone. Same pattern as ops.collectives
    # allgather_p: ``all_gather_invariant`` types the output replicated
    # under the varying-axes check (test_adasum.py pins the all-gather
    # lowering).
    gathered_seg = all_gather_invariant(seg, axis, axis=0, tiled=False)
    bits = p.bit_length() - 1

    def _bitrev(m: int) -> int:
        out = 0
        for k in range(bits):
            if m & (1 << k):
                out |= 1 << (bits - 1 - k)
        return out

    out = jnp.concatenate([gathered_seg[_bitrev(m)] for m in range(p)])

    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape).astype(orig_dtype)


def adasum_reference(tensors: Sequence[np.ndarray]) -> np.ndarray:
    """NumPy model of the Adasum reduction (test oracle; mirrors the model in
    ``test/test_adasum_pytorch.py``)."""
    vecs = [np.asarray(t, dtype=np.float64).reshape(-1) for t in tensors]
    n = len(vecs)
    p = 1
    while p * 2 <= n:
        p *= 2
    r = n - p
    for i in range(r):
        vecs[i] = vecs[i] + vecs[p + i]

    def rec(lo: int, count: int) -> np.ndarray:
        if count == 1:
            return vecs[lo]
        half = count // 2
        a = rec(lo, half)
        b = rec(lo + half, half)
        dot = float(np.dot(a, b))
        na2 = float(np.dot(a, a))
        nb2 = float(np.dot(b, b))
        a_coeff = 1.0 if na2 == 0 else 1.0 - dot / (2.0 * na2)
        b_coeff = 1.0 if nb2 == 0 else 1.0 - dot / (2.0 * nb2)
        return a_coeff * a + b_coeff * b

    out = rec(0, p)
    return out.reshape(np.asarray(tensors[0]).shape)
