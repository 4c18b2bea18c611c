"""Fused (flash) attention, causal, banded or bidirectional — Pallas TPU
kernels.

The reference is a collective-communication library and ships no attention
kernels; this is a TPU-first extension for the GPT / long-context path
(SURVEY.md §2.7: long-context is in scope for the rebuild). Plain attention
(``ops/attention.py default_attention``) materializes the full
``[B, H, S, S]`` fp32 logits tensor in HBM — at S=4096 that is ~2 GB per
layer per pass, which is exactly the HBM-bandwidth wall flash attention
exists to avoid. Algorithm: FlashAttention online-softmax tiling
(arXiv:2205.14135), with the standard recompute-from-logsumexp backward.

Design notes (TPU):

* Layout: q, o, dO, dQ are ``[B*H, S, D]``; k, v, dK, dV stay at their own
  head count, ``[B*Hkv, S, D]``. Grouped-query attention is an index map:
  query head ``h`` reads K/V head ``h // group``; nothing is repeated in
  HBM, and dK/dV of one K/V head accumulate over its whole group inside the
  backward kernel and are written once. A value head may have another width
  than a query and key head (latent attention: scores over 192 dimensions,
  values of 128): q, k, dQ, dK are ``D`` wide, v, o, dO, dV and the
  accumulator ``Dv``, each a block's whole minor axis (Mosaic takes a minor
  axis of one and a half lane tiles as it stands; nothing is padded), and
  the logits' scale is one over the root of ``D``. With ``Dv == D`` the
  kernels are the ones they were.
* The tile follows the call. ``block_sizes`` gives ``(block_q, block_k)``
  per kernel from what the trace can see (padded S, D, operand dtype,
  causal): the largest candidate that divides the padded length and whose
  VMEM estimate fits the limit the call sets. The candidates are a table
  in the source, from runs of the kernels alone on a v5e
  (``scripts/flash_block_sweep.py``; PERF.md, Findings, PR 24); nothing is
  searched at run time. The sequence is padded to 128 rows whatever the
  block.
* MXU operands have the input's dtype: ``q·kᵀ`` and ``dO·vᵀ`` take the refs
  as they are, ``p`` and ``ds`` are cast to it for the second products
  (what ``default_attention`` does with ``probs.astype(q.dtype)``); float32
  inputs get float32 products (at Mosaic's default precision, which on the
  v5e measured as one bfloat16 pass: PERF.md, Findings, PR 24). Everything
  else is float32 whatever the input: scores, max, exp, denominator,
  log-sum-exp, delta, accumulators.
* Each kernel walks a 3-D grid whose innermost dimension streams the
  contraction blocks: the forward visits ``(bh, q_block, k_block)`` with the
  online-softmax state (running max and denominator, lane-replicated
  ``[block_q, 128]``, and the output accumulator) carried across k-steps
  in VMEM scratch and written on the final visit — VMEM use is
  O(block x D + block_q x block_k) regardless of sequence length.
* Which pairs are kept is one description, :class:`Mask`: causal, a causal
  band (``window``: a query sees itself and the ``window - 1`` keys before
  it) or none. Causal mode skips the tiles wholly above the diagonal, a
  window besides those wholly below the band (``pl.when``: no FLOPs), and
  every tile computed is masked (masking only those the diagonal
  crosses measured no cheaper). A skipped step fetches nothing either: the
  index maps of the streamed operands clamp to the nearest kept tile of the
  row (forward, dQ) or column (dKdV), so a skipped step names a block
  already held and the pipeline issues no DMA. A query row whose band has
  not begun in the first tile its block visits sees a tile of masked
  scores there; the running maximum's next rise wipes what that added (the
  row's own diagonal tile always follows). The grid is the causal one: a
  skipped step still costs its fraction of a microsecond. Tail padding is
  free (a real query row
  never attends a key beyond itself). Bidirectional mode (``causal=False``,
  encoder models) computes every block and masks the padded key columns,
  where there are any. Any sequence length works in both.
* Backward = one kernel where a K/V head's dK and dV fit VMEM (every shape
  a benchmark cell runs; ``backward_is_fused``): it walks
  ``(b*hkv, group x q_block, k_block)``, the dQ kernel's order with the dKdV
  kernel's walk over the group's query heads, on the transposed score tile
  (``k·qᵀ``, so dV and dK are plain products and the row statistics come as
  ``[1, block_q]`` rows), and makes each kept tile once: five products
  (``k·qᵀ``, ``pᵀ·dO``, ``v·dOᵀ``, ``dsᵀ·q`` and, for dQ, ``kᵀ·dsᵀ``
  summed as ``dQᵀ`` over the row's k blocks) where two kernels made seven.
  dK and dV of the head's whole sequence are float32 sums in VMEM scratch,
  indexed by the k block and written out, scaled and cast, on the head's
  last step; every sum runs in the order the pair's ran, so the gradients
  are the pair's. In the compiled program it carries the dKdV kernel's
  name (the benchmark's readers of the backward pass match it). A sequence
  too long for that (somewhere past 16k rows at heads of 128) keeps the
  two kernels, same streaming structure: dKdV walks
  ``(b*hkv, k_block, group x q_block)``, dQ walks
  ``(bh, q_block, k_block)`` on the tile as the forward has it, with the
  statistics lane-replicated ``[block_q, 128]``; each
  recomputes the probability tile from q, k and the saved row logsumexp —
  no S x S tensor is ever materialized in either direction.
* Gate: compiled through Mosaic on the TPU backend, ``interpret=True`` on
  every other backend (the CPU-mesh tests): the package's one platform test,
  ``ops/pallas_util.py::on_tpu``.
  Interpret mode says nothing about Mosaic lowering; ``chip_smoke.py``
  leg B runs the forward and the one backward kernel compiled, inside a
  training step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .pallas_util import LANES, NEG_INF, NT, div as _div, \
    out_vma as _out_vma, rem as _rem, use_interpret as _use_interpret

_PAD = 128    # the sequence is padded to this many rows, whatever the block
# The kernels' names in the compiled program: each becomes the name of its
# HLO instruction, which is the name of its event in a device trace. The
# benchmark's readers match these strings (tests/test_program_names.py).
KERNEL_FWD = "hvd_flash_fwd"
KERNEL_DKDV = "hvd_flash_dkdv"
KERNEL_DQ = "hvd_flash_dq"
# What this module hands ``checkpoint_name``: the forward kernel's output and
# one lane of its log-sum-exp (2 H D + 4 H bytes a token a layer in
# bfloat16). A ``jax.checkpoint`` that keeps both (``save_only_these_names``)
# does not run the kernel a second time for the backward pass; with either
# missing it runs again.
SAVED_NAMES = ("flash_out", "flash_lse")

# The scoped VMEM every call asks Mosaic for (the default, 16 MiB, does not
# hold a 512-1024-wide float32 score tile); half of a v5e core's 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# The block table: a side of a tile is the largest of these that divides the
# padded length, for all three kernels. From runs of the kernels alone on a
# v5e at B*H,S,D = 48x4096x128 and 384x512x128 (and 2048, 1024 between),
# bfloat16, K/V at 2 of 24 heads (scripts/flash_block_sweep.py; PERF.md,
# Findings, PR 24): what a grid step costs beside its products decides, so
# the larger tile wins up to 1024 (2048 loses 5-15%: more of the tile is
# masked) and up to the whole of a short sequence (512x512 at S=512 is
# twice as fast as 256x256, though it computes the masked half too).
_CANDIDATES = (1024, 512, 256, 128)


def vmem_estimate(kernel: str, block_q: int, block_k: int, d: int,
                  itemsize: int, dv: Optional[int] = None,
                  fused_rows: Optional[int] = None) -> int:
    """Bytes of VMEM one grid step of ``kernel`` holds: every streamed block
    twice (the pipeline's double buffer), the scratch, and the score-sized
    temporaries of the body (float32, plus the casts to the operand dtype).
    ``d`` is the width of a query and key head (q, k, dQ, dK), ``dv`` that of
    a value head (v, o, dO, dV, the accumulator; None: ``d``); each counts
    as whole lane tiles. ``fused_rows`` (``KERNEL_DKDV`` only): the padded
    length, for the kernel that makes dQ too and holds dK and dV of a K/V
    head's whole sequence, the float32 sums and the blocks they leave
    through; None: the dKdV kernel of the pair."""
    d = -(-d // LANES) * LANES
    dv = d if dv is None else -(-dv // LANES) * LANES
    q_blk, k_blk = block_q * d, block_k * d
    o_blk, v_blk = block_q * dv, block_k * dv
    tile = block_q * block_k
    if kernel == KERNEL_FWD:
        blocks = (q_blk + o_blk + k_blk + v_blk) * itemsize \
            + block_q * LANES * 4
        scratch = (2 * block_q * LANES + o_blk) * 4
        temps = tile * (3 * 4 + itemsize)
    elif kernel == KERNEL_DKDV and fused_rows is not None:
        whole = fused_rows * (d + dv)
        blocks = (2 * q_blk + o_blk + k_blk + v_blk + whole) * itemsize \
            + 2 * 8 * block_q * 4
        scratch = (q_blk + whole) * 4
        temps = tile * (4 * 4 + 2 * itemsize)
    elif kernel == KERNEL_DKDV:
        blocks = (q_blk + o_blk + 2 * k_blk + 2 * v_blk) * itemsize \
            + 2 * 8 * block_q * 4
        scratch = (k_blk + v_blk) * 4
        temps = tile * (4 * 4 + 2 * itemsize)
    else:
        blocks = (2 * q_blk + o_blk + k_blk + v_blk) * itemsize \
            + 2 * block_q * LANES * 4
        scratch = q_blk * 4
        temps = tile * (4 * 4 + 2 * itemsize)
    return 2 * blocks + scratch + temps


def block_sizes(kernel: str, s_pad: int, d: int, dtype, causal: bool,
                dv: Optional[int] = None) -> tuple[int, int]:
    """``(block_q, block_k)`` of ``kernel`` for a call the trace sees as
    padded length ``s_pad``, query/key head size ``d``, value head size
    ``dv`` (None: ``d``), operand ``dtype``: the
    largest candidate that divides ``s_pad``, halved (the key side first)
    while the VMEM estimate is over the limit the call sets. A pure
    function of its arguments."""
    del causal  # the call can see it; the table does not split on it
    itemsize = jnp.dtype(dtype).itemsize
    bq = bk = next(c for c in _CANDIDATES if s_pad % c == 0)
    while vmem_estimate(kernel, bq, bk, d, itemsize, dv) > VMEM_LIMIT_BYTES:
        if bk >= bq and bk > _PAD:
            bk //= 2
        elif bq > _PAD:
            bq //= 2
        else:
            break
    return bq, bk


def backward_is_fused(block_q: int, block_k: int, s_pad: int, d: int, dtype,
                      dv: Optional[int] = None) -> bool:
    """Whether a call's backward pass is the one kernel that makes dQ, dK
    and dV from one score tile: where the float32 sums of a K/V head's whole
    dK and dV fit the VMEM the call asks for beside the tile
    (``vmem_estimate``; somewhere past 16k rows at heads of 128 they do
    not, and dKdV and dQ stay a kernel each). A pure function of its
    arguments, as ``block_sizes`` is."""
    return vmem_estimate(KERNEL_DKDV, block_q, block_k, d,
                         jnp.dtype(dtype).itemsize, dv,
                         fused_rows=s_pad) <= VMEM_LIMIT_BYTES


def _compiler_params(carried_over: int = 1):
    """The last ``carried_over`` grid axes carry sums in VMEM scratch."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (3 - carried_over)
        + ("arbitrary",) * carried_over,
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic widened (or cut) to
    ``[rows, n]``."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


@dataclasses.dataclass(frozen=True)
class Mask:
    """Which (query, key) pairs attention keeps: the one description the
    three kernels read, pair by pair (``keep``), tile by tile (``tile_kept``)
    and as the range of tiles a row or column of the grid visits
    (``k_blocks``, ``q_blocks``: what the index maps clamp to, so that a
    skipped step names a block already held and nothing is fetched for it).
    A new kind of mask (same-document: ROADMAP Reach 1) is a field here and
    a clause in each of these, not a flag threaded beside ``causal``.

    ``causal``: ``k <= q``. ``window`` (causal only): besides, ``q - k <
    window``, a query sees itself and the ``window - 1`` keys before it.
    Static: it rides the kernels' partial arguments and the custom VJP's
    non-differentiable ones."""
    causal: bool = True
    window: Optional[int] = None

    def __post_init__(self):
        if self.window is not None and (not self.causal or self.window < 1):
            raise ValueError("a window is a causal band of at least one "
                             f"key, got {self!r}")

    @property
    def name(self) -> str:
        return "window" if self.window is not None \
            else "causal" if self.causal else "full"

    def needs_masking(self, kv_len: int, s_pad: int) -> bool:
        """Every kept tile is masked alike (masking only the tiles the
        diagonal crosses measured no cheaper on the v5e: PERF.md, Findings,
        PR 24); a bidirectional call without padding has nothing to mask."""
        return self.causal or kv_len != s_pad

    def keep(self, q_pos, k_pos, kv_len: int):
        """The pairs kept, elementwise. Causal masks also cover the tail
        padding for free (a real query row never attends a key at or beyond
        its own position's pad); a bidirectional mask must shut the padded
        key columns out explicitly (``k_pos >= kv_len``), or every query
        would attend the zero-filled tail."""
        if not self.causal:
            return k_pos < kv_len
        keep = q_pos >= k_pos
        if self.window is not None:
            keep = keep & (q_pos - k_pos < self.window)
        return keep

    def tile_kept(self, qi, kj, block_q: int, block_k: int):
        """Whether the tile at block indices ``(qi, kj)`` holds any kept
        pair."""
        if not self.causal:
            # Trivially-true predicate, NOT an unguarded body: interpret
            # mode's vma tracing (CPU-mesh shard_map) only standardizes the
            # block-fetch slice's varying axes along the pl.when path — an
            # unguarded body trips "dynamic_slice requires varying manual
            # axes to match". Compiled Mosaic folds the constant predicate.
            return kj >= 0
        # The tile's last query row reaches its first key.
        kept = (qi + 1) * block_q - 1 >= kj * block_k
        if self.window is not None:
            # Its first query row still sees its last key.
            kept = kept & ((kj + 1) * block_k + self.window - 2
                           >= qi * block_q)
        return kept

    def k_blocks(self, i, block_q: int, block_k: int):
        """``(first, last)`` k block that query block ``i`` attends, either
        None where the grid's own end is the bound."""
        if not self.causal:
            return None, None
        first = None if self.window is None else _div(
            jnp.maximum(i * block_q - (self.window - 1), 0), block_k)
        return first, _div((i + 1) * block_q - 1, block_k)

    def q_blocks(self, j, block_q: int, block_k: int):
        """``(first, last)`` q block that attends k block ``j``."""
        if not self.causal:
            return None, None
        last = None if self.window is None else _div(
            (j + 1) * block_k + self.window - 2, block_q)
        return _div(j * block_k, block_q), last

    def tiles(self, n_q: int, n_k: int, block_q: int, block_k: int) -> dict:
        """How a ``n_q x n_k`` grid's tiles fall: ``kept`` (computed),
        ``skipped`` (wholly above the diagonal) and ``skipped_band``
        (wholly below the band: what a window saves of the causal
        triangle's). Python integers, for the trace-time counter."""
        out = {"kept": 0, "skipped": 0, "skipped_band": 0}
        for i in range(n_q):
            for j in range(n_k):
                if self.tile_kept(i, j, block_q, block_k):
                    out["kept"] += 1
                elif j * block_k > (i + 1) * block_q - 1:
                    out["skipped"] += 1
                else:
                    out["skipped_band"] += 1
        return out


def _clamp(index, first, last):
    """``index`` held inside ``[first, last]`` (either may be None): what an
    index map names on a skipped step."""
    if last is not None:
        index = jnp.minimum(index, last)
    if first is not None:
        index = jnp.maximum(index, first)
    return index


def _mask_tile(s, q_start, k_start, mask: Mask, kv_len: int,
               transposed: bool = False):
    """Mask a score tile whose first query row and key column are at global
    positions ``q_start``/``k_start``; ``transposed`` tiles are [keys,
    queries]."""
    q_dim, k_dim = (1, 0) if transposed else (0, 1)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_dim)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim) \
        if mask.causal else None
    return jnp.where(mask.keep(q_pos, k_pos, kv_len), s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, block_q: int, block_k: int,
                n_k_blocks: int, mask: Mask, kv_len: int, masked: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    d = acc_scr.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step():
        v = v_ref[0]                                     # [BK, D]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], NT,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                 # [BQ, BK] float32
        if masked:
            s = _mask_tile(s, qi * block_q, kj * block_k, mask, kv_len)
        m_prev = m_scr[:]                                # [BQ, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * _lanes(alpha, d) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    pl.when(mask.tile_kept(qi, kj, block_q, block_k))(_step)

    @pl.when(kj == n_k_blocks - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[:] / _lanes(safe_l, d)).astype(o_ref.dtype)
        # Lane-replicated [BQ, 128]: Mosaic requires output block shapes
        # whose last two dims are (8, 128)-tileable — a [BQ]-vector block
        # is rejected on a real chip (interpret mode hid this). Same
        # layout as jax's bundled TPU flash kernel's l/m stats
        # (pallas/ops/tpu/flash_attention.py, MIN_BLOCK_SIZE lanes).
        lse_ref[0] = m_scr[:] + jnp.log(safe_l)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                 block_q: int, block_k: int, n_q_blocks: int, n_steps: int,
                 mask: Mask, kv_len: int, masked: bool):
    kj = pl.program_id(1)
    step = pl.program_id(2)        # (query head of the group, q block)
    qi = _rem(step, n_q_blocks)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step():
        q = q_ref[0]                                     # [BQ, D]
        do = do_ref[0]
        # The tile transposed, [BK, BQ]: dV and dK are then plain products,
        # and the row statistics broadcast down from [1, BQ] rows.
        st = jax.lax.dot_general(k_ref[0], q, NT,
                                 preferred_element_type=jnp.float32)
        st = st * sm_scale
        if masked:
            st = _mask_tile(st, qi * block_q, kj * block_k, mask, kv_len,
                            transposed=True)
        pt = jnp.exp(st - lse_ref[0])
        dv_scr[:] = dv_scr[:] + jnp.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0])
        dk_scr[:] = dk_scr[:] + jnp.dot(
            dst.astype(q.dtype), q, preferred_element_type=jnp.float32)

    pl.when(mask.tile_kept(qi, kj, block_q, block_k))(_step)

    @pl.when(step == n_steps - 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale: float, block_q: int, block_k: int,
               n_k_blocks: int, mask: Mask, kv_len: int, masked: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step():
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, NT,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if masked:
            s = _mask_tile(s, qi * block_q, kj * block_k, mask, kv_len)
        p = jnp.exp(s - _lanes(lse_ref[0], block_k))
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta_ref[0], block_k))
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    pl.when(mask.tile_kept(qi, kj, block_q, block_k))(_step)

    @pl.when(kj == n_k_blocks - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                sm_scale: float, block_q: int, block_k: int,
                n_q_blocks: int, n_k_blocks: int, n_steps: int, mask: Mask,
                kv_len: int, masked: bool):
    """dQ, dK and dV from one score tile: the dKdV kernel's transposed tile
    and its walk over the group's query heads, on the dQ kernel's grid. dK
    and dV of the K/V head's whole sequence are float32 sums in VMEM."""
    step = pl.program_id(1)        # (query head of the group, q block)
    kj = pl.program_id(2)
    qi = _rem(step, n_q_blocks)
    first, last = kj == 0, kj == n_k_blocks - 1

    @pl.when(first & (step == 0))
    def _init_head():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(first)
    def _init_row():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step():
        q = q_ref[0]                                     # [BQ, D]
        k = k_ref[0]                                     # [BK, D]
        do = do_ref[0]
        rows = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
        st = jax.lax.dot_general(k, q, NT,
                                 preferred_element_type=jnp.float32)
        st = st * sm_scale                               # [BK, BQ] float32
        if masked:
            st = _mask_tile(st, qi * block_q, kj * block_k, mask, kv_len,
                            transposed=True)
        pt = jnp.exp(st - lse_ref[0])
        dv_scr[rows, :] = dv_scr[rows, :] + jnp.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0])).astype(q.dtype)
        dk_scr[rows, :] = dk_scr[rows, :] + jnp.dot(
            dst, q, preferred_element_type=jnp.float32)
        # The one product over the tile's first axis, dQ = dstᵀ·k, summed
        # transposed, kᵀ·dst: the [BK, D] side is the one turned, not the
        # tile (as fast or faster at every cell's shape: PERF.md, PR 52).
        dq_scr[:] = dq_scr[:] + jnp.dot(
            k.T, dst, preferred_element_type=jnp.float32)

    pl.when(mask.tile_kept(qi, kj, block_q, block_k))(_step)

    @pl.when(last)
    def _finish_row():
        dq_ref[0] = (dq_scr[:].T * sm_scale).astype(dq_ref.dtype)

    @pl.when(last & (step == n_steps - 1))
    def _finish_head():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pad_seq(x):
    pad = (-x.shape[1]) % _PAD
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _blocks_for(kernel, q, k, v, mask: Mask, forced, dq: str = "own"):
    """The call's tile, forced or from the table; and, trace time only, the
    record of it (with the width of a query/key head and of a value head,
    and where dQ is made: ``fused`` on a dKdV kernel that makes it too,
    ``own`` on the pair's two, ``none`` on the forward) and of the tiles
    its grid keeps and skips behind ``hvd.metrics()``."""
    bq, bk = forced or block_sizes(kernel, q.shape[1], q.shape[2], q.dtype,
                                   mask.causal, v.shape[2])
    runtime.note_traced(
        "hvdtpu_spmd_flash_kernel_traces_total", kernel=kernel, block_q=bq,
        block_k=bk, operand_dtype=jnp.dtype(q.dtype).name,
        kv_group=q.shape[0] // k.shape[0], key_dim=q.shape[2],
        value_dim=v.shape[2], dq=dq)
    for tiles, n in mask.tiles(q.shape[1] // bq, q.shape[1] // bk,
                               bq, bk).items():
        runtime.note_traced(
            "hvdtpu_spmd_flash_tiles_total", n, kernel=kernel,
            mask=mask.name, tiles=tiles, seq=q.shape[1])
    return bq, bk


def _kv_map(group: int, block_q: int, block_k: int, mask: Mask):
    """Index map of K and V on a ``(bh, q_block, k_block)`` grid: the K/V
    head of query head ``b``; a skipped step re-names the nearest kept
    block, so the pipeline issues no DMA for it."""
    def kv_map(b, i, j):
        j = _clamp(j, *mask.k_blocks(i, block_q, block_k))
        return _div(b, group), j, 0
    return kv_map


def _fwd_call(q, k, v, sm_scale, mask, kv_len, forced=None):
    """q: [B*H, S, D], k: [B*Hkv, S, D], v: [B*Hkv, S, Dv] (S already
    padded; ``kv_len`` is the real key count before padding; ``mask`` a
    :class:`Mask`). Returns (o [B*H, S, Dv], lse), lse lane-replicated
    [B*H, S, 128]."""
    bh, s, d = q.shape
    dv = v.shape[2]
    group = bh // k.shape[0]
    bq, bk = _blocks_for(KERNEL_FWD, q, k, v, mask, forced, dq="none")
    n_q, n_k = s // bq, s // bk

    kv_map = _kv_map(group, bq, bk, mask)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, block_q=bq,
                               block_k=bk, n_k_blocks=n_k, mask=mask,
                               kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            # lse rides lane-replicated [bh, s, 128] (see _fwd_kernel).
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype,
                                 vma=_out_vma(q, k, v)),
            jax.ShapeDtypeStruct((bh, s, LANES), jnp.float32,
                                 vma=_out_vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, dv), jnp.float32),       # output accumulator
        ],
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=KERNEL_FWD,
    )(q, k, v)


def _dkdv_call(q, k, v, do, lse, delta, sm_scale, mask, kv_len,
               forced=None):
    """dK, dV at the K/V head count (``v``, ``do`` and dV as wide as a
    value head). ``lse``/``delta``: [B*H, 1, S] rows."""
    bh, s, d = q.shape
    dv = v.shape[2]
    bkv = k.shape[0]
    group = bh // bkv
    bq, bk = _blocks_for(KERNEL_DKDV, q, k, v, mask, forced)
    n_q, n_k = s // bq, s // bk

    def q_block(b, j, t):
        # Step t of a k block: query head t // n_q of the group, q block
        # t % n_q; a skipped q block re-names the nearest kept one.
        i = _clamp(_rem(t, n_q), *mask.q_blocks(j, bq, bk))
        return b * group + _div(t, n_q), i

    def q_map(b, j, t):
        return (*q_block(b, j, t), 0)

    def row_map(b, j, t):
        head, i = q_block(b, j, t)
        return head, 0, i

    kernel = functools.partial(_dkdv_kernel, sm_scale=sm_scale, block_q=bq,
                               block_k=bk, n_q_blocks=n_q,
                               n_steps=group * n_q, mask=mask,
                               kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    vma = _out_vma(q, k, v, do)
    return pl.pallas_call(
        kernel,
        grid=(bkv, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),                           # q
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),       # k
            pl.BlockSpec((1, bk, dv), lambda b, j, t: (b, j, 0)),      # v
            pl.BlockSpec((1, bq, dv), q_map),                          # do
            pl.BlockSpec((1, 1, bq), row_map),                         # lse
            pl.BlockSpec((1, 1, bq), row_map),                         # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, s, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bkv, s, dv), v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=KERNEL_DKDV,
    )(q, k, v, do, lse, delta)


def _dq_call(q, k, v, do, lse, delta, sm_scale, mask, kv_len, forced=None):
    """dQ (``v`` and ``do`` as wide as a value head). ``lse``/``delta``:
    lane-replicated [B*H, S, 128]."""
    bh, s, d = q.shape
    dv = v.shape[2]
    group = bh // k.shape[0]
    bq, bk = _blocks_for(KERNEL_DQ, q, k, v, mask, forced)
    n_q, n_k = s // bq, s // bk

    kv_map = _kv_map(group, bq, bk, mask)

    def q_map(b, i, j):
        return b, i, 0

    kernel = functools.partial(_dq_kernel, sm_scale=sm_scale, block_q=bq,
                               block_k=bk, n_k_blocks=n_k, mask=mask,
                               kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),                           # q
            pl.BlockSpec((1, bk, d), kv_map),                          # k
            pl.BlockSpec((1, bk, dv), kv_map),                         # v
            pl.BlockSpec((1, bq, dv), q_map),                          # do
            pl.BlockSpec((1, bq, LANES), q_map),                      # lse
            pl.BlockSpec((1, bq, LANES), q_map),                      # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype,
                                       vma=_out_vma(q, k, v, do)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=KERNEL_DQ,
    )(q, k, v, do, lse, delta)


def _bwd_call(q, k, v, do, lse, delta, sm_scale, mask, kv_len, forced=None):
    """dQ at the query head count, dK and dV at the K/V head count, from one
    kernel (``hvd_flash_dkdv`` in the compiled program: what the
    benchmark's readers of the backward pass match). ``lse``/``delta``:
    [B*H, 1, S] rows."""
    bh, s, d = q.shape
    dv = v.shape[2]
    bkv = k.shape[0]
    group = bh // bkv
    bq, bk = _blocks_for(KERNEL_DKDV, q, k, v, mask, forced, dq="fused")
    n_q, n_k = s // bq, s // bk

    def q_block(b, t):
        # Step t of a K/V head: query head t // n_q of its group, q block
        # t % n_q.
        return b * group + _div(t, n_q), _rem(t, n_q)

    def q_map(b, t, j):
        return (*q_block(b, t), 0)

    def row_map(b, t, j):
        head, i = q_block(b, t)
        return head, 0, i

    def kv_map(b, t, j):
        # A skipped step re-names the nearest kept block of the row.
        return b, _clamp(j, *mask.k_blocks(_rem(t, n_q), bq, bk)), 0

    def whole_map(b, t, j):
        return b, 0, 0

    kernel = functools.partial(_bwd_kernel, sm_scale=sm_scale, block_q=bq,
                               block_k=bk, n_q_blocks=n_q, n_k_blocks=n_k,
                               n_steps=group * n_q, mask=mask, kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    vma = _out_vma(q, k, v, do)
    return pl.pallas_call(
        kernel,
        grid=(bkv, group * n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),                           # q
            pl.BlockSpec((1, bk, d), kv_map),                          # k
            pl.BlockSpec((1, bk, dv), kv_map),                         # v
            pl.BlockSpec((1, bq, dv), q_map),                          # do
            pl.BlockSpec((1, 1, bq), row_map),                         # lse
            pl.BlockSpec((1, 1, bq), row_map),                         # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            # A K/V head's whole dK and dV: the index is constant over the
            # two inner axes, so they leave VMEM once, on its last step.
            pl.BlockSpec((1, s, d), whole_map),
            pl.BlockSpec((1, s, dv), whole_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bkv, s, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bkv, s, dv), v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, bq), jnp.float32),       # dQ of the row, transposed
            pltpu.VMEM((s, d), jnp.float32),        # the K/V head's dK
            pltpu.VMEM((s, dv), jnp.float32),       # ... and dV
        ],
        compiler_params=_compiler_params(carried_over=2),
        interpret=_use_interpret(),
        name=KERNEL_DKDV,
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd(q, k, v, sm_scale, mask, kv_len, forced):
    o, _ = _fwd_call(q, k, v, sm_scale, mask, kv_len, forced)
    return o


def _flash_bhsd_fwd(q, k, v, sm_scale, mask, kv_len, forced):
    o, lse = _fwd_call(q, k, v, sm_scale, mask, kv_len, forced)
    # Named, both (``SAVED_NAMES``), so that a ``jax.checkpoint`` around the
    # caller can keep them and not run this kernel a second time for the
    # backward pass; outside a checkpoint a name is an identity.
    o = checkpoint_name(o, "flash_out")
    # Residual carries ONE lane of the lane-replicated stats: holding the
    # [bh, s, 128] form across the whole fwd->bwd interval would cost 128x
    # the logical bytes per layer; the backward re-broadcasts transiently.
    return o, (q, k, v, o, checkpoint_name(lse[..., 0], "flash_lse"))


def _flash_bhsd_bwd(sm_scale, mask, kv_len, forced, res, do):
    q, k, v, o, lse = res
    bh, s, d = q.shape
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # The statistics enter a transposed tile as [bh, 1, s] rows.
    rows = lse[:, None, :], delta[:, None, :]
    tile = forced or block_sizes(KERNEL_DKDV, s, d, q.dtype, mask.causal,
                                 v.shape[2])
    if backward_is_fused(*tile, s, d, q.dtype, v.shape[2]):
        return _bwd_call(q, k, v, do, *rows, sm_scale, mask, kv_len, tile)
    # Too long for a head's dK and dV to stay in VMEM: a kernel each. dQ
    # reads the statistics lane-replicated [bh, s, 128] (Mosaic rejects
    # vector blocks whose sublane dim is 1 — see _fwd_kernel), transiently:
    # the residual holds one float a row.
    dk, dv = _dkdv_call(q, k, v, do, *rows, sm_scale, mask, kv_len, forced)
    dq = _dq_call(q, k, v, do,
                  jnp.broadcast_to(lse[..., None], (bh, s, LANES)),
                  jnp.broadcast_to(delta[..., None], (bh, s, LANES)),
                  sm_scale, mask, kv_len, forced)
    return dq, dk, dv


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(q, k, v, causal: bool = True, *,
                    window: Optional[int] = None, _blocks=None):
    """Fused attention. q: ``[B, S, H, D]`` (the layout the GPT blocks
    use); k/v: ``[B, S, Hkv, D]`` where ``Hkv`` may divide ``H``
    (grouped-query attention: the kernels read K/V head ``h // group`` for
    query head ``h``, nothing is repeated, and dK/dV come back at ``Hkv``
    heads); v may be ``[B, S, Hkv, Dv]`` with ``Dv != D``, and the output
    is then ``[B, S, H, Dv]`` (the scores are over ``D`` and scaled by one
    over its root). Differentiable (custom VJP, flash backward).

    ``causal=True`` (decoder) skips the tiles above the diagonal;
    ``causal=False`` (encoder/bidirectional) computes all blocks with the
    tail padding masked out of the key axis. ``window`` (static, causal
    only): a query sees itself and the ``window - 1`` keys before it, and
    the tiles wholly below that band are skipped as those above the
    diagonal are, neither multiplied nor fetched, in every kernel; a
    window of the sequence's length or more is the causal program, unchanged
    (:class:`Mask`). Tile sizes and the MXU operands' dtype follow the
    call's shapes and dtype (``block_sizes``); ``_blocks=(block_q,
    block_k)`` forces one tile on every kernel, for the tests and the
    sweep.
    """
    b, s, h, d = q.shape
    if k.shape[2] != v.shape[2] or h % k.shape[2]:
        raise ValueError(f"query heads ({h}) not a multiple of kv heads "
                         f"(k {k.shape[2]}, v {v.shape[2]})")
    if k.shape[3] != d:
        raise ValueError(f"a key head is as wide as a query head ({d}), "
                         f"got {k.shape[3]}; a value head may differ")
    if _blocks is not None:
        _blocks = tuple(int(x) for x in _blocks)
        s_pad = s + (-s) % _PAD
        if any(x % _PAD or s_pad % x for x in _blocks):
            raise ValueError(f"blocks {_blocks} must be multiples of {_PAD} "
                             f"that divide the padded length {s_pad}")
    sm_scale = 1.0 / float(np.sqrt(d))
    mask = Mask(bool(causal),
                None if window is None or window >= s else int(window))

    def to_bhsd(x):
        return _pad_seq(x.transpose(0, 2, 1, 3).reshape(-1, s, x.shape[3]))

    o = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), sm_scale, mask, s,
                    _blocks)
    return o[:, :s, :].reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)
