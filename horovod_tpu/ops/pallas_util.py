"""What the Pallas kernel families share, said once: the tile's widths, the
masked exponent, the two contraction patterns, the platform test and a few
helpers. The floor of ``ops/``: it imports nothing of the package, and every
kernel module (``flash_attention``, ``ssd``, ``s6``, ``gated_delta``, ``kda``,
``conv``, ``cca``) imports these names from here and no kernel's part from
another (``cca`` takes two plain references, ``conv.causal_conv1d`` and
``attention.rope``, and ``kda`` one, ``gated_delta.unit_rows``, for the lines
their kernels are held to; what the two delta rules' kernels share, the
unit-triangular inverse in VMEM, its packing in HBM and the rows' norm, is
here)."""

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


def to_lanes(t, *axes: int):
    """``t`` with each of ``axes`` (the last, if none is named) padded with
    zeros to the next multiple of the lane width, at which the kernels carry
    a key or value head; ``t`` itself where they are multiples already."""
    pad = [(0, 0)] * t.ndim
    for axis in axes or (-1,):
        pad[axis] = (0, -t.shape[axis] % LANES)
    return jnp.pad(t, pad) if any(extra for _, extra in pad) else t


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


def row_sum(t):
    """``[Q, X]`` summed along the lanes: a column ``[Q, 1]``."""
    return jnp.sum(t, axis=1, keepdims=True)


NORM_EPS = 1e-6  # added to a row's sum of squares where a scan's kernels
                 # L2-normalise q and k (``norm_qk``: gated_delta, kda)


def raw_row_cotangents(raws, invs, cotangents, scales):
    """The float32 cotangents of rows as they came, for those of the rows a
    kernel normed in VMEM: for ``n = t r``, ``r = rsqrt(|t|^2 + eps)``
    (``invs``), ``dt = r (dn - n <dn, n>)``, times the row's ``scale``."""
    out = []
    for raw, inv, dn, scale in zip(raws, invs, cotangents, scales):
        n = raw.astype(jnp.float32) * inv
        out.append((dn - n * row_sum(dn * n)) * (inv * scale))
    return out


def column_as_row(diagonal, column):
    """A column ``[Q, 1]`` as a row ``[1, Q]``, through the ``[Q, Q]``
    diagonal mask (Mosaic transposes no vector)."""
    return jnp.sum(jnp.where(diagonal, column, 0.0), axis=0, keepdims=True)


def unit_lower_inverse_in_vmem(a, substitute: int = 32):
    """``(I + a)^{-1}`` of one ``[n, n]`` float32 matrix as a kernel can
    trace it (masks from ``iota``, no captured constant), full float32
    throughout, ``a`` strictly lower triangular (the gated delta rule's and
    Kimi delta attention's ``T``: ``ops/gated_delta.py``, ``ops/kda.py``).
    The diagonal blocks of ``substitute`` rows by forward
    substitution on the vector unit, a column a step: ``(I + a) X = I``
    with ``X`` starting as ``I``; at step ``j`` row ``j`` is final and ``X_i
    -= a_ij X_j`` for the rows below it in its block (eight-row tiles, those
    above ``j`` skipped). Then the inverse by blocks from that block
    size up (``D_2b = D_b - D_b L_b D_b``, ``L_b`` the lower left ``b x b``
    block of every ``2b x 2b`` diagonal block), every product on the MXU at
    the highest precision. ``substitute`` is a multiple of eight, or 1: no
    substitution, every round a product."""
    size = a.shape[-1]
    block, tile = min(substitute, size), min(8, size)
    rows = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    cols = lax.broadcasted_iota(jnp.int32, (size, size), 1)

    def lower_left(shift):
        """Block size ``b = 2 ** shift``: the lower left blocks."""
        return ((rows >> (shift + 1)) == (cols >> (shift + 1))) \
            & (((rows >> shift) & 1) == 1) & (((cols >> shift) & 1) == 0)

    inv = jnp.where(rows == cols, 1.0, 0.0).astype(a.dtype)
    at = [slice(t * tile, (t + 1) * tile) for t in range(size // tile)]
    x, a_t = [inv[at_t] for at_t in at], [a[at_t] for at_t in at]
    for j in range(size):
        x_j = x[j // tile][j % tile:j % tile + 1]
        for t in range((j + 1) // tile, (j // block + 1) * block // tile):
            x[t] = x[t] - a_t[t][:, j:j + 1] * x_j
    inv = jnp.concatenate(x, axis=0)
    for shift in range(block.bit_length() - 1, size.bit_length() - 1):
        left = jnp.dot(inv, jnp.where(lower_left(shift), a, 0.0),
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=a.dtype)
        inv = inv - jnp.dot(left, inv, precision=lax.Precision.HIGHEST,
                            preferred_element_type=a.dtype)
    return inv


def t_pack(chunk: int) -> int:
    """Blocks of ``T``'s rows that lie side by side in HBM (the delta rules'
    kept inverse, ``ops/gated_delta.py`` and ``ops/kda.py``): a ``[64, 64]``
    float32 tile as it stands is half padding there (the lanes are 128), so
    its lower 32 rows go beside its upper 32, ``[32, 128]``. As many as fill
    the lanes and leave whole eight-row tiles; 1 (``T`` as it is) from a
    chunk of 128 up and under 16."""
    return max(1, min(LANES // chunk, chunk // 8))


def pack_t(t):
    """``T`` ``[Q, Q]`` as the kernels keep it, ``[Q / pack, pack Q]``."""
    pack = t_pack(t.shape[0])
    rows = t.shape[0] // pack
    return t if pack == 1 else jnp.concatenate(
        [t[i * rows:(i + 1) * rows] for i in range(pack)], axis=1)


def unpack_t(kept, chunk: int):
    """:func:`pack_t` undone, ``[..., Q / pack, pack Q]`` -> ``[..., Q,
    Q]``: in the backward kernel, and on the whole array where a test reads
    what the forward kernel wrote."""
    return kept if kept.shape[-1] == chunk else jnp.concatenate(
        [kept[..., i:i + chunk] for i in range(0, kept.shape[-1], chunk)],
        axis=-2)
