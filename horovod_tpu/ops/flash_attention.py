"""Fused (flash) causal attention — Pallas TPU kernels.

The reference is a collective-communication library and ships no attention
kernels; this is a TPU-first extension for the GPT / long-context path
(SURVEY.md §2.7: long-context is in scope for the rebuild). Plain attention
(``models/transformer.py default_attention``) materializes the full
``[B, H, S, S]`` fp32 logits tensor in HBM — at S=4096 that is ~2 GB per
layer per pass, which is exactly the HBM-bandwidth wall flash attention
exists to avoid. Algorithm: FlashAttention online-softmax tiling
(arXiv:2205.14135), with the standard recompute-from-logsumexp backward.

Design notes (TPU):

* Layout ``[B*H, S, D]``. Each kernel walks a 3-D grid whose innermost
  dimension streams the contraction blocks: the forward visits
  ``(bh, q_block, k_block)`` so only ONE ``BLOCK x D`` slab of K and V is
  DMA'd into VMEM per step, with the online-softmax state (running max,
  denominator, output accumulator) carried across k-steps in VMEM scratch
  and written on the final visit — VMEM use is O(BLOCK x D) regardless of
  sequence length, not O(S x D).
* All matmuls accumulate in fp32 (``preferred_element_type``) on the MXU.
* Causal mode skips the upper-triangle blocks entirely (``pl.when`` — no
  DMA, no FLOPs) and gets tail-padding to the 128-row block for free (a
  real query row never attends a key beyond itself). Bidirectional mode
  (``causal=False``, encoder models) computes every block and masks the
  padded key columns instead. Any sequence length works in both.
* Backward = two kernels, same streaming structure: dKdV walks
  ``(bh, k_block, q_block)``, dQ walks ``(bh, q_block, k_block)``, each
  recomputing the probability tile from q, k and the saved row logsumexp —
  no S x S tensor is ever materialized in either direction.
* Gate: compiled through Mosaic on the TPU backend, ``interpret=True`` on
  every other backend (the CPU-mesh tests) — the same gate as the quantize
  kernels (``compression/quantize.py`` ``_pallas_backend_enabled``).
  Interpret mode says nothing about Mosaic lowering; ``chip_smoke.py``
  leg B runs all three kernels compiled, inside a training step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
_LANES = 128  # TPU lane width: softmax stats ride lane-replicated [*, 128]
_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/where NaN-free
# The kernels' names in the compiled program: each becomes the name of its
# HLO instruction, which is the name of its event in a device trace. The
# benchmark's readers match these strings (tests/test_program_names.py).
KERNEL_FWD = "hvd_flash_fwd"
KERNEL_DKDV = "hvd_flash_dkdv"
KERNEL_DQ = "hvd_flash_dq"


def _use_interpret() -> bool:
    # Same gate as the quantize kernels: compiled on the TPU backend only;
    # everything else (the CPU-mesh tests) runs the interpreter.
    from ..compression.quantize import _pallas_backend_enabled
    return not _pallas_backend_enabled(None)


from .pallas_util import out_vma as _out_vma  # noqa: E402


def repeat_kv_heads(k, n_q_heads: int):
    """Grouped-query attention: tile K/V heads up to the query head count
    (the compact heads are what cross the wire; the repeat is local).
    Shared by flash, ring and Ulysses attention."""
    n_kv = k.shape[2]
    if n_kv == n_q_heads:
        return k
    if n_q_heads % n_kv:
        raise ValueError(
            f"query heads ({n_q_heads}) not a multiple of kv heads ({n_kv})")
    return jnp.repeat(k, n_q_heads // n_kv, axis=2)


def _mask_tile(s, q_block, k_block, causal: bool, kv_len: int):
    """Mask logits tile ``s`` [BLOCK_Q, BLOCK_K] (global positions from the
    block indices). Causal mode masks the upper triangle — which also
    covers the tail padding for free (a real query row never attends a key
    at or beyond its own position's pad). Non-causal mode must mask the
    padded key columns explicitly (``k_pos >= kv_len``), or every query
    would attend the zero-filled tail."""
    k_pos = k_block * BLOCK_K + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    if causal:
        q_pos = q_block * BLOCK_Q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        return jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return jnp.where(k_pos < kv_len, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, n_k_blocks: int, causal: bool,
                kv_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [BQ, D]
        k = k_ref[0].astype(jnp.float32)                 # [BK, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_tile(s, qi, kj, causal, kv_len)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    if causal:
        # Upper-triangle blocks contribute nothing — skip their DMA+FLOPs.
        pl.when(kj <= qi)(_step)
    else:
        # Trivially-true predicate, NOT a bare _step() call: interpret
        # mode's vma tracing (CPU-mesh shard_map) only standardizes the
        # block-fetch slice's varying axes along the pl.when path — an
        # unguarded body trips "dynamic_slice requires varying manual
        # axes to match". Compiled Mosaic folds the constant predicate.
        pl.when(kj >= 0)(_step)

    @pl.when(kj == n_k_blocks - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # Lane-replicated [BQ, 128]: Mosaic requires output block shapes
        # whose last two dims are (8, 128)-tileable — a [BQ]-vector block
        # is rejected on a real chip (interpret mode hid this). Same
        # layout as jax's bundled TPU flash kernel's l/m stats
        # (pallas/ops/tpu/flash_attention.py, MIN_BLOCK_SIZE lanes).
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(safe_l),
                                      (m_scr.shape[0], _LANES))


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                 n_q_blocks: int, causal: bool, kv_len: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step():
        k = k_ref[0].astype(jnp.float32)                 # [BK, D]
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [BQ, D]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]    # lane-replicated stats: any lane works
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_tile(s, qi, kj, causal, kv_len)
        p = jnp.exp(s - lse)                             # [BQ, BK]
        # dv += p^T @ dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dk += ds^T @ q  (q already carries sm_scale)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Earlier query blocks never see these keys — skip them.
        pl.when(qi >= kj)(_step)
    else:
        pl.when(qi >= 0)(_step)  # trivially true; see _fwd_kernel note

    @pl.when(qi == n_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale: float, n_k_blocks: int, causal: bool,
               kv_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_tile(s, qi, kj, causal, kv_len)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(kj <= qi)(_step)
    else:
        pl.when(kj >= 0)(_step)  # trivially true; see _fwd_kernel note

    @pl.when(kj == n_k_blocks - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _pad_seq(x, block):
    s = x.shape[1]
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _fwd_call(q, k, v, sm_scale, causal, kv_len, interpret):
    """q/k/v: [BH, S, D] (S already padded; ``kv_len`` is the real key
    count before padding). Returns (o, lse)."""
    bh, s, d = q.shape
    n_q = s // BLOCK_Q
    n_k = s // BLOCK_K
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                               n_k_blocks=n_k, causal=causal,
                               kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0)),
            # lse rides lane-replicated [bh, s, 128] (see _fwd_kernel).
            pl.BlockSpec((1, BLOCK_Q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype,
                                 vma=_out_vma(q, k, v)),
            jax.ShapeDtypeStruct((bh, s, _LANES), jnp.float32,
                                 vma=_out_vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),   # running max
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((BLOCK_Q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name=KERNEL_FWD,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, sm_scale, causal, kv_len):
    o, _ = _fwd_call(q, k, v, sm_scale, causal, kv_len, _use_interpret())
    return o


def _flash_bhsd_fwd(q, k, v, sm_scale, causal, kv_len):
    o, lse = _fwd_call(q, k, v, sm_scale, causal, kv_len, _use_interpret())
    # Residual carries ONE lane of the lane-replicated stats: holding the
    # [bh, s, 128] form across the whole fwd->bwd interval would cost 128x
    # the logical bytes per layer; the backward re-broadcasts transiently.
    return o, (q, k, v, o, lse[..., :1])


def _flash_bhsd_bwd(sm_scale, causal, kv_len, res, do):
    q, k, v, o, lse = res
    interpret = _use_interpret()
    bh, s, d = q.shape
    n_q = s // BLOCK_Q
    n_k = s // BLOCK_K
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass, XLA fuses it.
    # Both stats enter the kernels lane-replicated [bh, s, 128] (Mosaic
    # rejects vector blocks whose sublane dim is 1 — see _fwd_kernel) but
    # only transiently for the backward: the residual holds one lane.
    lse = jnp.broadcast_to(lse, (bh, s, _LANES))
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1, keepdims=True), (bh, s, _LANES))

    dkdv = functools.partial(_dkdv_kernel, sm_scale=sm_scale,
                             n_q_blocks=n_q, causal=causal, kv_len=kv_len)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, j, i: (b, i, 0)),  # q
            pl.BlockSpec((1, BLOCK_K, d), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, BLOCK_K, d), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, j, i: (b, i, 0)),  # do
            pl.BlockSpec((1, BLOCK_Q, _LANES),
                         lambda b, j, i: (b, i, 0)),                   # lse
            pl.BlockSpec((1, BLOCK_Q, _LANES),
                         lambda b, j, i: (b, i, 0)),                   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_K, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BLOCK_K, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype,
                                 vma=_out_vma(q, k, v, do)),
            jax.ShapeDtypeStruct((bh, s, d), q.dtype,
                                 vma=_out_vma(q, k, v, do)),
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK_K, d), jnp.float32),
            pltpu.VMEM((BLOCK_K, d), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_DKDV,
    )(q, k, v, do, lse, delta)

    dqk = functools.partial(_dq_kernel, sm_scale=sm_scale, n_k_blocks=n_k,
                            causal=causal, kv_len=kv_len)
    dq = pl.pallas_call(
        dqk,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, BLOCK_Q, _LANES),
                         lambda b, i, j: (b, i, 0)),                   # lse
            pl.BlockSpec((1, BLOCK_Q, _LANES),
                         lambda b, i, j: (b, i, 0)),                   # delta
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype,
                                       vma=_out_vma(q, k, v, do)),
        scratch_shapes=[pltpu.VMEM((BLOCK_Q, d), jnp.float32)],
        interpret=interpret,
        name=KERNEL_DQ,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(q, k, v, causal: bool = True):
    """Fused attention. q: ``[B, S, H, D]`` (the layout the GPT blocks
    use); k/v: ``[B, S, Hkv, D]`` where ``Hkv`` may divide ``H``
    (grouped-query attention — kv heads tile up locally, mirroring ring
    attention's contract). Differentiable (custom VJP, flash backward).

    ``causal=True`` (decoder) skips the upper-triangle blocks entirely;
    ``causal=False`` (encoder/bidirectional) computes all blocks with the
    tail padding masked out of the key axis.
    """
    # GQA: repeat before the kernel (no-op when heads match; also
    # validates BOTH k and v against the query head count).
    k = repeat_kv_heads(k, q.shape[2])
    v = repeat_kv_heads(v, q.shape[2])
    b, s, h, d = q.shape
    sm_scale = 1.0 / float(np.sqrt(d))

    def to_bhsd(x):
        return _pad_seq(x.transpose(0, 2, 1, 3).reshape(b * h, s, d),
                        BLOCK_Q)

    o = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), sm_scale,
                    bool(causal), s)
    return o[:, :s, :].reshape(b, h, s, d).transpose(0, 2, 1, 3)
