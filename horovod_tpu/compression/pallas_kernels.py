"""Pallas TPU kernels for the quantization hot path.

Reference: the CUDA kernels in
``horovod/common/ops/compressed/compression/cuda/cuda_compression_functions.cu``
(826 LoC — quantize/dequantize/add device kernels). On TPU these are Pallas
kernels: bucket rows live in VMEM, min/max reductions run on the VPU, and the
quantized codes are written as uint8 — XLA fuses the surrounding pack/unpack.

Kernels also run under ``interpret=True`` for CPU-mesh tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BUCKET_BLOCK = 256  # buckets per grid step (BUCKET_BLOCK x bucket_size fp32)
# The kernels' names in the compiled program: each becomes the name of its
# HLO instruction, which is the name of its event in a device trace
# (tests/test_program_names.py pins the strings).
KERNEL_MAXMIN_QUANTIZE = "hvd_maxmin_quantize"
KERNEL_MAXMIN_QUANTIZE_STOCHASTIC = "hvd_maxmin_quantize_stochastic"
KERNEL_MAXMIN_DEQUANTIZE = "hvd_maxmin_dequantize"
KERNEL_MAXMIN_DEQUANTIZE_SUM = "hvd_maxmin_dequantize_sum"
KERNEL_NORM_QUANTIZE = "hvd_norm_quantize"
KERNEL_NORM_DEQUANTIZE = "hvd_norm_dequantize"


from ..ops.pallas_util import out_vma as _out_vma  # noqa: E402


def _quantize_kernel(levels: int, x_ref, q_ref, mn_ref, unit_ref):
    x = x_ref[:]
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    unit = (mx - mn) / levels
    safe = jnp.where(unit == 0, 1.0, unit)
    q = jnp.clip(jnp.round((x - mn) / safe), 0, levels)
    # Mosaic has no f32->u8 cast; hop through i32 (verified on v5e).
    q_ref[:] = q.astype(jnp.int32).astype(jnp.uint8)
    mn_ref[:] = mn
    unit_ref[:] = unit


def _dequantize_kernel(x_ref, mn_ref, unit_ref, out_ref):
    # u8 -> i32 -> f32: Mosaic supports no direct 8-bit <-> f32 casts.
    codes = x_ref[:].astype(jnp.int32).astype(jnp.float32)
    out_ref[:] = mn_ref[:] + codes * unit_ref[:]


def _norm_quantize_kernel(use_l2: bool, n_levels: int, x_ref, levels_ref,
                          q_ref, norm_ref):
    """Nearest-level norm quantization (reference: CPUNormalizedQuantizer,
    compressor.h:219). The level search runs as an L-iteration running
    argmin over the block in VMEM — the XLA fallback materializes the full
    [block, bucket, L] distance tensor instead (L x the HBM traffic)."""
    x = x_ref[:]
    if use_l2:
        norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    else:
        norm = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    safe = jnp.where(norm == 0, 1.0, norm)
    ratio = jnp.abs(x) / safe

    def body(i, carry):
        best_d, best_i = carry
        d = jnp.abs(ratio - levels_ref[i])
        take = d < best_d
        return (jnp.where(take, d, best_d),
                jnp.where(take, i, best_i))

    best_d0 = jnp.abs(ratio - levels_ref[0])
    best_i0 = jnp.zeros(x.shape, jnp.int32)
    _, best_i = jax.lax.fori_loop(1, n_levels, body, (best_d0, best_i0))
    # Pack in i32 (8-bit shifts/ors don't lower on Mosaic), cast last.
    sign = (x < 0).astype(jnp.int32)
    q_ref[:] = ((best_i << 1) | sign).astype(jnp.uint8)
    norm_ref[:] = norm


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def norm_quantize_pallas(flat: jnp.ndarray, levels: jnp.ndarray,
                         bucket_size: int, use_l2: bool,
                         interpret: bool = False):
    """Bucket-wise norm quantization on the TPU; returns
    (q [n_buckets, bucket_size] uint8 with sign in bit 0, norm [n_buckets]).
    """
    from jax.experimental.pallas import tpu as pltpu

    n = flat.shape[0]
    n_buckets = -(-n // bucket_size)
    grid = -(-n_buckets // BUCKET_BLOCK)
    padded_buckets = grid * BUCKET_BLOCK
    padded = jnp.zeros((padded_buckets * bucket_size,), jnp.float32)
    padded = padded.at[:n].set(flat)
    x = padded.reshape(padded_buckets, bucket_size)

    q, norm = pl.pallas_call(
        functools.partial(_norm_quantize_kernel, use_l2,
                          int(levels.shape[0])),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_buckets, bucket_size), jnp.uint8,
                                 vma=_out_vma(x)),
            jax.ShapeDtypeStruct((padded_buckets, 1), jnp.float32,
                                 vma=_out_vma(x)),
        ],
        interpret=interpret,
        name=KERNEL_NORM_QUANTIZE,
    )(x, levels.astype(jnp.float32))
    return q[:n_buckets], norm[:n_buckets, 0]


def _norm_dequantize_kernel(n_levels: int, q_ref, levels_ref, norm_ref,
                            out_ref):
    q = q_ref[:].astype(jnp.int32)  # widen first: no 8-bit bit-ops on Mosaic
    # Clamp like the XLA fallback (quantize.py decompress): a payload from a
    # larger table decompressed after set_quantization_levels installed a
    # smaller one must reconstruct at the last level, not silently as 0.
    idx = jnp.clip(q >> 1, 0, n_levels - 1)
    sign = 1.0 - 2.0 * (q & 1).astype(jnp.float32)

    def body(i, acc):
        return acc + jnp.where(idx == i, levels_ref[i], 0.0)

    vals = jax.lax.fori_loop(0, n_levels, body,
                             jnp.zeros(q.shape, jnp.float32))
    out_ref[:] = sign * vals * norm_ref[:]


@functools.partial(jax.jit, static_argnums=(3,))
def norm_dequantize_pallas(q: jnp.ndarray, levels: jnp.ndarray,
                           norm: jnp.ndarray, interpret: bool = False):
    """Inverse of :func:`norm_quantize_pallas`:
    [n_buckets, bucket] uint8 -> fp32 via an L-iteration table expansion."""
    from jax.experimental.pallas import tpu as pltpu

    n_buckets, bucket = q.shape
    grid = -(-n_buckets // BUCKET_BLOCK)
    padded_buckets = grid * BUCKET_BLOCK
    qp = jnp.zeros((padded_buckets, bucket), jnp.uint8).at[:n_buckets].set(q)
    np_ = jnp.zeros((padded_buckets, 1), jnp.float32)\
        .at[:n_buckets, 0].set(norm)

    out = pl.pallas_call(
        functools.partial(_norm_dequantize_kernel, int(levels.shape[0])),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BUCKET_BLOCK, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_buckets, bucket),
                                       jnp.float32,
                                       vma=_out_vma(qp, np_)),
        interpret=interpret,
        name=KERNEL_NORM_DEQUANTIZE,
    )(qp, levels.astype(jnp.float32), np_)
    return out[:n_buckets]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def maxmin_quantize_pallas(flat: jnp.ndarray, bits: int, bucket_size: int,
                           interpret: bool = False):
    """Quantize a flat fp32 vector bucket-wise on the TPU.

    Returns (q [n_buckets, bucket_size] uint8, min [n_buckets], unit
    [n_buckets]); caller packs bits / truncates padding.
    """
    n = flat.shape[0]
    n_buckets = -(-n // bucket_size)
    grid = -(-n_buckets // BUCKET_BLOCK)
    padded_buckets = grid * BUCKET_BLOCK
    padded = jnp.zeros((padded_buckets * bucket_size,), jnp.float32)
    padded = padded.at[:n].set(flat)
    x = padded.reshape(padded_buckets, bucket_size)
    levels = (1 << bits) - 1

    q, mn, unit = pl.pallas_call(
        functools.partial(_quantize_kernel, levels),
        grid=(grid,),
        in_specs=[pl.BlockSpec((BUCKET_BLOCK, bucket_size),
                               lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_buckets, bucket_size), jnp.uint8,
                                 vma=_out_vma(x)),
            jax.ShapeDtypeStruct((padded_buckets, 1), jnp.float32,
                                 vma=_out_vma(x)),
            jax.ShapeDtypeStruct((padded_buckets, 1), jnp.float32,
                                 vma=_out_vma(x)),
        ],
        interpret=interpret,
        name=KERNEL_MAXMIN_QUANTIZE,
    )(x)
    return (q[:n_buckets], mn[:n_buckets, 0], unit[:n_buckets, 0])


def _quantize_stochastic_kernel(levels: int, x_ref, seed_ref, q_ref, mn_ref,
                                unit_ref):
    from jax.experimental.pallas import tpu as pltpu

    # Decorrelate grid blocks: same seed + program id.
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    x = x_ref[:]
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    unit = (mx - mn) / levels
    safe = jnp.where(unit == 0, 1.0, unit)
    scaled = (x - mn) / safe
    # Uniform [0,1) from 24 PRNG bits (reference: the fork's xorshift path,
    # cuda_rand.h + GPU_RAND in cuda_compression_functions.cu).
    # prng_random_bits returns SIGNED int32: mask (not shift) — an
    # arithmetic shift would put u in [-0.5, 0.5) and bias every rounding
    # down by half a unit.
    bits = pltpu.prng_random_bits(x.shape)
    u = (bits & 0xffffff).astype(jnp.float32) * (1.0 / (1 << 24))
    q = jnp.clip(jnp.floor(scaled + u), 0, levels)
    q_ref[:] = q.astype(jnp.int32).astype(jnp.uint8)
    mn_ref[:] = mn
    unit_ref[:] = unit


@functools.partial(jax.jit, static_argnums=(1, 2))
def maxmin_quantize_stochastic_pallas(flat: jnp.ndarray, bits: int,
                                      bucket_size: int, seed: jnp.ndarray):
    """Stochastic-rounding max-min quantization on the TPU PRNG
    (reference: ``cuda_rand.h`` xorshift + ``QUANTIZE`` kernels in
    ``cuda_compression_functions.cu``). TPU-only: CPU-mesh tests use the
    XLA fallback (``pltpu.prng_*`` has no CPU lowering).

    Returns (q [n_buckets, bucket_size] uint8, min [n_buckets],
    unit [n_buckets]).
    """
    from jax.experimental.pallas import tpu as pltpu

    n = flat.shape[0]
    n_buckets = -(-n // bucket_size)
    grid = -(-n_buckets // BUCKET_BLOCK)
    padded_buckets = grid * BUCKET_BLOCK
    padded = jnp.zeros((padded_buckets * bucket_size,), jnp.float32)
    padded = padded.at[:n].set(flat)
    x = padded.reshape(padded_buckets, bucket_size)
    levels = (1 << bits) - 1

    q, mn, unit = pl.pallas_call(
        functools.partial(_quantize_stochastic_kernel, levels),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_buckets, bucket_size), jnp.uint8,
                                 vma=_out_vma(x, seed)),
            jax.ShapeDtypeStruct((padded_buckets, 1), jnp.float32,
                                 vma=_out_vma(x, seed)),
            jax.ShapeDtypeStruct((padded_buckets, 1), jnp.float32,
                                 vma=_out_vma(x, seed)),
        ],
        name=KERNEL_MAXMIN_QUANTIZE_STOCHASTIC,
    )(x, seed.reshape(1).astype(jnp.int32))
    return (q[:n_buckets], mn[:n_buckets, 0], unit[:n_buckets, 0])


def _dequantize_sum_kernel(x_ref, mn_ref, unit_ref, out_ref):
    # x: [n_ranks, BLOCK, bucket] uint8; accumulate all ranks' dequantized
    # values in one VMEM pass (reference: the dequant+add inner loops of
    # the compressed reducers, cuda_compression_functions.cu).
    x = x_ref[:].astype(jnp.int32).astype(jnp.float32)
    total = jnp.sum(x * unit_ref[:], axis=0) + jnp.sum(mn_ref[:], axis=0)
    out_ref[:] = total


@functools.partial(jax.jit, static_argnums=(3,))
def maxmin_dequantize_sum_pallas(q: jnp.ndarray, mn: jnp.ndarray,
                                 unit: jnp.ndarray, interpret: bool = False):
    """Fused dequantize-and-sum over the ranks axis:
    ``q [n_ranks, n_buckets, bucket]`` uint8 + per-rank ``mn``/``unit``
    ``[n_ranks, n_buckets]`` -> fp32 ``[n_buckets, bucket]`` summed over
    ranks — one kernel instead of n dequantize programs + n adds."""
    n_ranks, n_buckets, bucket = q.shape
    grid = -(-n_buckets // BUCKET_BLOCK)
    padded_buckets = grid * BUCKET_BLOCK
    qp = jnp.zeros((n_ranks, padded_buckets, bucket), jnp.uint8)\
        .at[:, :n_buckets].set(q)
    mnp = jnp.zeros((n_ranks, padded_buckets, 1), jnp.float32)\
        .at[:, :n_buckets, 0].set(mn)
    up = jnp.zeros((n_ranks, padded_buckets, 1), jnp.float32)\
        .at[:, :n_buckets, 0].set(unit)

    out = pl.pallas_call(
        _dequantize_sum_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n_ranks, BUCKET_BLOCK, bucket),
                         lambda i: (0, i, 0)),
            pl.BlockSpec((n_ranks, BUCKET_BLOCK, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((n_ranks, BUCKET_BLOCK, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((BUCKET_BLOCK, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_buckets, bucket), jnp.float32,
                                       vma=_out_vma(qp, mnp, up)),
        interpret=interpret,
        name=KERNEL_MAXMIN_DEQUANTIZE_SUM,
    )(qp, mnp, up)
    return out[:n_buckets]


@functools.partial(jax.jit, static_argnums=(3, 4))
def maxmin_dequantize_pallas(q: jnp.ndarray, mn: jnp.ndarray,
                             unit: jnp.ndarray, bucket_size: int,
                             interpret: bool = False):
    """Inverse kernel: [n_buckets, bucket_size] uint8 -> fp32."""
    n_buckets = q.shape[0]
    grid = -(-n_buckets // BUCKET_BLOCK)
    padded_buckets = grid * BUCKET_BLOCK
    qp = jnp.zeros((padded_buckets, bucket_size), jnp.uint8).at[:n_buckets].set(q)
    mnp = jnp.zeros((padded_buckets, 1), jnp.float32).at[:n_buckets, 0].set(mn)
    up = jnp.zeros((padded_buckets, 1), jnp.float32).at[:n_buckets, 0].set(unit)

    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
            pl.BlockSpec((BUCKET_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BUCKET_BLOCK, bucket_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_buckets, bucket_size),
                                       jnp.float32,
                                       vma=_out_vma(qp, mnp, up)),
        interpret=interpret,
        name=KERNEL_MAXMIN_DEQUANTIZE,
    )(qp, mnp, up)
    return out[:n_buckets]
