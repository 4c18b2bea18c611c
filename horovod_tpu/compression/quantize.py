"""Bucketed lossy gradient quantizers + top-k sparsification.

Reference: the IST-DASLab compression subsystem,
``horovod/common/ops/compressed/compression/compressor.{h,cc}`` —
``CPUMaxMinQuantizer`` (h:168, bucket-wise linear quantization to b bits),
``CPUNormalizedQuantizer`` (h:219, norm-scaled quantization against a level
table, uniform or exponential, with L2/Linf norms), ``GPUTopKCompressor``
(gpu_compressor.h), stochastic rounding RNG (``cuda/cuda_rand.h``), default
bucket size 512 (compressor.h:11).

TPU-native redesign: quantize/dequantize are pure functions of arrays (usable
under jit / shard_map / grad-stopped paths), with a Pallas TPU kernel for the
hot path on TPU backends (:mod:`horovod_tpu.compression.pallas_kernels`) and
an XLA implementation that non-TPU backends and ``use_pallas=False`` select.
The choice is made up front from the backend; a Pallas kernel that fails on a
TPU raises. Payloads are bit-packed uint8 so the wire size actually shrinks
(reference packs on GPU in ``cuda_compression_functions.cu``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import pallas_util

DEFAULT_BUCKET_SIZE = 512  # reference: compressor.h:11


# ---------------------------------------------------------------------------
# bit packing
# ---------------------------------------------------------------------------

def pack_bits(q: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack uint8 values (< 2**bits) into a dense uint8 array; ``bits`` must
    divide 8. Zero-pads to a multiple of 8//bits values per byte group."""
    if bits == 8:
        return q.astype(jnp.uint8)
    per = 8 // bits
    rem = q.shape[0] % per
    if rem:
        q = jnp.concatenate([q, jnp.zeros((per - rem,), q.dtype)])
    # Strided slices, not reshape(-1, per): XLA's TPU compiler spends ~40 ms
    # per 1000 elements on a uint8 -> uint32 convert of the [N/per, per]
    # reshape whenever N is not a multiple of 4096 (85 s for a 1.8M-element
    # gradient, AOT-compiled for v5e) — and a gradient's size never is.
    packed = q[0::per].astype(jnp.uint32)
    for j in range(1, per):
        packed = packed | (q[j::per].astype(jnp.uint32) << (j * bits))
    return packed.astype(jnp.uint8)


def unpack_bits(p: jnp.ndarray, bits: int, count: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`; returns the first ``count`` values."""
    if bits == 8:
        return p[:count]
    per = 8 // bits
    shifts = jnp.arange(per, dtype=jnp.uint32) * bits
    vals = (p.astype(jnp.uint32)[:, None] >> shifts[None, :]) & ((1 << bits) - 1)
    return vals.reshape(-1)[:count].astype(jnp.uint8)


def _pallas_backend_enabled(override: Optional[bool]) -> bool:
    """The quantizers' use-Pallas gate: an explicit override wins, else the
    kernel layer's platform test (the kernels have no CPU lowering outside
    interpret mode)."""
    if override is not None:
        return override
    return pallas_util.on_tpu()


def _seed_from_key(key: Optional[jax.Array]) -> jnp.ndarray:
    """An int32 seed for the TPU hardware PRNG from a JAX PRNG key (typed or
    raw uint32 data); zero when no key is given (deterministic noise)."""
    if key is None:
        return jnp.zeros((), jnp.int32)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.reshape(-1)[-1].astype(jnp.int32)


def _bucketize(flat: jnp.ndarray, bucket_size: int) -> Tuple[jnp.ndarray, int]:
    """Pad + reshape a flat vector into (n_buckets, bucket_size)."""
    n = flat.shape[0]
    n_buckets = -(-n // bucket_size)
    padded = jnp.zeros((n_buckets * bucket_size,), flat.dtype).at[:n].set(flat)
    return padded.reshape(n_buckets, bucket_size), n


# ---------------------------------------------------------------------------
# quantizers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantContext:
    """Static metadata needed to invert a quantized payload."""
    shape: Tuple[int, ...]
    dtype: object
    count: int
    bits: int
    bucket_size: int


class MaxMinQuantizer:
    """Bucket-wise linear quantization to ``bits`` bits
    (reference: ``CPUMaxMinQuantizer``, compressor.h:168)::

        unit = (max - min) / (2**bits - 1)
        q    = round((x - min) / unit)        (stochastic: floor(. + u))
        x'   = min + q * unit

    ``compress`` returns ``(payload_dict, ctx)`` where payload is a pytree of
    arrays (packed codes + per-bucket min/unit) that collectives can move.
    """

    def __init__(self, bits: int = 8, bucket_size: int = DEFAULT_BUCKET_SIZE,
                 stochastic: bool = False, use_pallas: Optional[bool] = None):
        if bits not in (1, 2, 4, 8):
            raise ValueError("bits must be one of 1, 2, 4, 8 (byte packing)")
        self.bits = bits
        self.bucket_size = bucket_size
        self.stochastic = stochastic
        self._use_pallas = use_pallas

    # Equal-config quantizers hash equal so the eager compiled-program cache
    # (reducers._eager_compressed_fn) dedupes across instances — the TPU
    # analog of the reference keying reducers off env config, not objects.
    def _cache_key(self):
        return ("maxmin", self.bits, self.bucket_size, self.stochastic,
                self._use_pallas)

    def __hash__(self):
        return hash(self._cache_key())

    def __eq__(self, other):
        return isinstance(other, MaxMinQuantizer) and \
            other._cache_key() == self._cache_key()

    def _pallas_enabled(self) -> bool:
        return _pallas_backend_enabled(self._use_pallas)

    def compress(self, x: jnp.ndarray, key: Optional[jax.Array] = None):
        ctx = QuantContext(shape=tuple(x.shape), dtype=x.dtype,
                           count=int(np.prod(x.shape)) if x.shape else 1,
                           bits=self.bits, bucket_size=self.bucket_size)
        flat = x.reshape(-1).astype(jnp.float32)
        if self._pallas_enabled():
            from . import pallas_kernels as pk
            if self.stochastic:
                # TPU-PRNG stochastic rounding (reference: the fork's
                # xorshift CUDA path, cuda_rand.h); the pltpu PRNG has no
                # lowering off the TPU.
                q, mn, unit = pk.maxmin_quantize_stochastic_pallas(
                    flat, self.bits, self.bucket_size, _seed_from_key(key))
            else:
                q, mn, unit = pk.maxmin_quantize_pallas(
                    flat, self.bits, self.bucket_size)
            payload = {"q": pack_bits(q.reshape(-1), self.bits),
                       "min": mn, "unit": unit}
            return payload, ctx
        buckets, n = _bucketize(flat, self.bucket_size)
        mn = jnp.min(buckets, axis=1, keepdims=True)
        mx = jnp.max(buckets, axis=1, keepdims=True)
        levels = (1 << self.bits) - 1
        unit = (mx - mn) / levels
        safe_unit = jnp.where(unit == 0, 1.0, unit)
        scaled = (buckets - mn) / safe_unit
        if self.stochastic:
            if key is None:
                key = jax.random.PRNGKey(0)
            noise = jax.random.uniform(key, scaled.shape)
            q = jnp.floor(scaled + noise)
        else:
            q = jnp.round(scaled)
        q = jnp.clip(q, 0, levels).astype(jnp.uint8)
        payload = {"q": pack_bits(q.reshape(-1), self.bits),
                   "min": mn[:, 0], "unit": unit[:, 0]}
        return payload, ctx

    def decompress(self, payload, ctx: QuantContext) -> jnp.ndarray:
        q = unpack_bits(payload["q"], ctx.bits,
                        -(-ctx.count // ctx.bucket_size) * ctx.bucket_size)
        buckets = q.reshape(-1, ctx.bucket_size).astype(jnp.float32)
        mn = payload["min"].reshape(-1, 1)
        unit = payload["unit"].reshape(-1, 1)
        out = mn + buckets * unit
        return out.reshape(-1)[:ctx.count].reshape(ctx.shape).astype(ctx.dtype)


# Level tables (reference: CPUNormalizedQuantizer levels — uniform/exponential,
# overridable at runtime via hvd.set_quantization_levels, operations.cc:909).
_user_levels: dict = {}


def set_quantization_levels(levels, for_type: str = "uni") -> None:
    """Override the norm-quantizer level table
    (reference: ``horovod_set_quantization_levels``, operations.cc:909;
    Python surface ``basics.py:261``). ``levels`` must be descending and end
    near 0; the first entry is scaled to 1.0."""
    arr = np.asarray(levels, dtype=np.float32).reshape(-1)
    if arr.size < 2:
        raise ValueError("need at least 2 levels")
    _user_levels[for_type] = arr / arr[0]


def default_levels(bits: int, kind: str) -> np.ndarray:
    if kind in _user_levels:
        return _user_levels[kind]
    n = 1 << (bits - 1)  # one bit goes to the sign
    if kind == "uni":
        return np.linspace(1.0, 0.0, n, dtype=np.float32)
    if kind == "exp":
        lv = np.array([2.0 ** -i for i in range(n - 1)] + [0.0],
                      dtype=np.float32)
        return lv
    raise ValueError(f"unknown level kind {kind!r}")


class NormalizedQuantizer:
    """Norm-scaled quantization against a level table
    (reference: ``CPUNormalizedQuantizer``, compressor.h:219): per bucket,
    ``x ≈ sign(x) * norm * level[q]`` with norm = Linf or L2 and levels
    uniform ("uni") or exponential ("exp")."""

    def __init__(self, bits: int = 4, bucket_size: int = DEFAULT_BUCKET_SIZE,
                 levels: str = "uni", norm: str = "linf",
                 use_pallas: Optional[bool] = None):
        if bits not in (2, 4, 8):
            raise ValueError("bits must be 2, 4 or 8")
        if norm not in ("l2", "linf"):
            # Fail fast like the other knobs: a typo ("l1") would otherwise
            # silently quantize against the linf path.
            raise ValueError(f"norm must be 'l2' or 'linf', got {norm!r}")
        self.bits = bits
        self.bucket_size = bucket_size
        self.kind = levels
        self.norm = norm
        self._use_pallas = use_pallas

    def _pallas_enabled(self) -> bool:
        return _pallas_backend_enabled(self._use_pallas)

    def _cache_key(self):
        # The user level table is part of identity: set_quantization_levels
        # must invalidate cached compiled programs that baked the old table.
        lv = _user_levels.get(self.kind)
        return ("norm", self.bits, self.bucket_size, self.kind, self.norm,
                self._use_pallas,
                None if lv is None else lv.tobytes())

    def __hash__(self):
        return hash(self._cache_key())

    def __eq__(self, other):
        return isinstance(other, NormalizedQuantizer) and \
            other._cache_key() == self._cache_key()

    def _levels(self) -> jnp.ndarray:
        levels = default_levels(self.bits, self.kind)
        max_levels = 1 << (self.bits - 1)
        if levels.shape[0] > max_levels:
            raise ValueError(
                f"level table has {levels.shape[0]} entries but bits="
                f"{self.bits} can index at most {max_levels} — the packed "
                "index would overflow into neighboring values (did "
                "set_quantization_levels install a table too large for this "
                "quantizer?)")
        return jnp.asarray(levels)

    def compress(self, x: jnp.ndarray, key: Optional[jax.Array] = None):
        ctx = QuantContext(tuple(x.shape), x.dtype,
                           int(np.prod(x.shape)) if x.shape else 1,
                           self.bits, self.bucket_size)
        flat = x.reshape(-1).astype(jnp.float32)
        if self._pallas_enabled():
            from . import pallas_kernels as pk
            q, norms = pk.norm_quantize_pallas(
                flat, self._levels(), self.bucket_size, self.norm == "l2")
            payload = {"q": pack_bits(q.reshape(-1), self.bits),
                       "norm": norms}
            return payload, ctx
        buckets, _ = _bucketize(flat, self.bucket_size)
        if self.norm == "l2":
            norms = jnp.sqrt(jnp.sum(buckets * buckets, axis=1, keepdims=True))
        else:
            norms = jnp.max(jnp.abs(buckets), axis=1, keepdims=True)
        safe = jnp.where(norms == 0, 1.0, norms)
        ratio = jnp.abs(buckets) / safe  # in [0, 1] for linf
        levels = self._levels()  # descending
        # nearest level index
        dist = jnp.abs(ratio[..., None] - levels[None, None, :])
        idx = jnp.argmin(dist, axis=-1).astype(jnp.uint8)
        sign = (buckets < 0).astype(jnp.uint8)
        # sign goes into the low bit, level index above it
        q = (idx << 1) | sign
        payload = {"q": pack_bits(q.reshape(-1), self.bits),
                   "norm": norms[:, 0]}
        return payload, ctx

    def decompress(self, payload, ctx: QuantContext) -> jnp.ndarray:
        padded = -(-ctx.count // ctx.bucket_size) * ctx.bucket_size
        q = unpack_bits(payload["q"], ctx.bits, padded)
        if self._pallas_enabled():
            from . import pallas_kernels as pk
            out = pk.norm_dequantize_pallas(
                q.reshape(-1, ctx.bucket_size), self._levels(),
                payload["norm"].reshape(-1))
            return out.reshape(-1)[:ctx.count].reshape(ctx.shape)\
                .astype(ctx.dtype)
        sign = 1.0 - 2.0 * (q & 1).astype(jnp.float32)
        idx = (q >> 1).astype(jnp.int32)
        levels = self._levels()
        vals = levels[jnp.clip(idx, 0, levels.shape[0] - 1)]
        buckets = (sign * vals).reshape(-1, ctx.bucket_size)
        out = buckets * payload["norm"].reshape(-1, 1)
        return out.reshape(-1)[:ctx.count].reshape(ctx.shape).astype(ctx.dtype)


class TopKCompressor:
    """Keep the top ``ratio`` fraction of entries by magnitude
    (reference: ``GPUTopKCompressor``, ``topk_compression.cu``; ratio knob
    ``HOROVOD_COMPRESSION_TOPK_RATIO``)."""

    def __init__(self, ratio: float = 0.01):
        if not 0 < ratio <= 1:
            raise ValueError("ratio must be in (0, 1]")
        self.ratio = ratio

    def _cache_key(self):
        return ("topk", self.ratio)

    def __hash__(self):
        return hash(self._cache_key())

    def __eq__(self, other):
        return isinstance(other, TopKCompressor) and \
            other._cache_key() == self._cache_key()

    def compress(self, x: jnp.ndarray, key=None):
        ctx = QuantContext(tuple(x.shape), x.dtype,
                           int(np.prod(x.shape)) if x.shape else 1, 32, 0)
        flat = x.reshape(-1).astype(jnp.float32)
        k = max(1, int(flat.shape[0] * self.ratio))
        vals_abs, idx = jax.lax.top_k(jnp.abs(flat), k)
        vals = flat[idx]
        return {"values": vals, "indices": idx.astype(jnp.int32)}, ctx

    def decompress(self, payload, ctx: QuantContext) -> jnp.ndarray:
        out = jnp.zeros((ctx.count,), jnp.float32)
        out = out.at[payload["indices"]].set(payload["values"])
        return out.reshape(ctx.shape).astype(ctx.dtype)


def compressed_size_bytes(payload) -> int:
    """Wire size of a compressed payload (for autotune scoring / tests)."""
    return sum(int(np.prod(p.shape)) * p.dtype.itemsize
               for p in jax.tree.leaves(payload))
