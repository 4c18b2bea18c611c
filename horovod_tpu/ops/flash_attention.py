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
  head count, ``[B*Hkv, S, D]``; or, where ``flash_attention``'s caller
  asks (``heads_major``), the same at rank 4, ``[B, H, S, D]`` and
  ``[B, Hkv, S, D]``: the same bytes in the same order, reached from a
  model's ``[B, S, H, D]`` by a transpose alone, which XLA folds into the
  layouts of the array's producers and consumers, where the merge to rank 3
  costs a copy of every operand, output and cotangent (``_dims``; PERF.md,
  Findings, PR 70). Grouped-query attention is an index map:
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
* Each kernel's grid is a head (or a K/V head and, an axis of their own in
  the one backward kernel, the query heads of its group), and as its last
  axis the tiles of a head's ``q_block x k_block`` rectangle that hold a
  kept pair, and no others. Which pairs are kept is one description, :class:`Mask`: causal, a
  causal band (``window``: a query sees itself and the ``window - 1`` keys
  before it), the block-diffusion mask over a noised and a clean copy of a
  sequence (``block_diffusion``) or none. The mask is static and so is the
  tile, so the mask
  itself enumerates its kept tiles at trace time (``Mask.kept_tiles``), a
  query block's tiles together with the keys ascending, and the list rides
  into the kernel as a scalar-prefetch table in SMEM
  (``pltpu.PrefetchScalarGridSpec``): every index map reads its block index
  from it, and the body reads its position and whether the step opens or
  closes its row. The forward carries the online-softmax state (running max
  and denominator, lane-replicated ``[block_q, 128]``, and the output
  accumulator) across a row's steps in VMEM scratch and writes on the row's
  last, so VMEM use is O(block x D + block_q x block_k) regardless of
  sequence length. A tile wholly above the diagonal or wholly below the
  band is no grid step: it is neither multiplied, nor fetched, nor walked
  (until PR 61 the grid was the rectangle and such a step cost its 0.3 µs).
  Every tile computed is masked (masking only those the diagonal crosses
  measured no cheaper). A query row whose band has not begun in the first
  tile its block visits sees a tile of masked scores there; the running
  maximum's next rise wipes what that added (the row's own diagonal tile
  always follows). Tail padding is free (a real query row never attends a
  key beyond itself). A bidirectional mask (``causal=False``, encoder
  models) keeps the whole rectangle, through the same code, and masks the
  padded key columns, where there are any. Any sequence length works in
  both.
* Backward = one kernel where a K/V head's dK and dV fit VMEM (every shape
  a benchmark cell runs; ``backward_is_fused``): it walks
  ``(b*hkv, query head of the group, kept tile)``, the dQ kernel's order
  under each query head of the group in turn, on the transposed score tile
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
  two kernels, same streaming structure: dKdV walks the kept tiles a k
  block's column at a time (the column once a query head of the group,
  ``_over_group``), dQ walks them as the forward does, with the
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
from .pallas_util import LANES, NEG_INF, NT, always, div as _div, \
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
# Under block diffusion, a noised key's block as the clean-key clause of
# ``Mask.keep`` reads it: past every block a query has.
_NO_BLOCK = 1 << 30


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


def _compiler_params(rank: int = 2):
    """A grid is (a head or a K/V head, ..., the kept tiles it walks): every
    axis after the first carries sums in VMEM scratch."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (rank - 1),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


# Rows of the table of kept tiles a grid walks (``Mask.kept_tiles``), and the
# one ``_over_group`` adds for the dKdV kernel of the pair.
TILE_Q, TILE_K, TILE_FIRST, TILE_LAST, TILE_HEAD = range(5)


def _at(tiles: np.ndarray, ref, row: int, t):
    """Row ``row`` of the table ``tiles`` at step ``t``, read from ``ref``
    (the table as the kernel holds it, in SMEM); where the whole row is one
    value, that value as a Python integer. A sequence of one tile a head is
    such a table (every step block 0, its row's first and last), and Mosaic
    then folds positions, the mask and the ``pl.when`` of a step as it
    folded the program id of a grid axis of one: read from SMEM they cost a
    512 x 512 tile's call a tenth of its time (PERF.md, Findings, PR 61)."""
    values = tiles[row]
    return int(values[0]) if (values == values[0]).all() else ref[row, t]


def _tile_at(tiles: np.ndarray, ref, t):
    """Step ``t`` of the table: its q block, its k block, and whether it is
    its run's first and last (1 or 0)."""
    return tuple(_at(tiles, ref, row, t)
                 for row in (TILE_Q, TILE_K, TILE_FIRST, TILE_LAST))


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
    three kernels read, pair by pair (``keep``), and the only thing their
    grids are built from: the tiles that hold a kept pair, listed at trace
    time in the order a kernel walks them (``kept_tiles``, by ``tile_kept``).
    A new kind of mask (same-document: ROADMAP Reach 1) is a field here: a
    static one changes the list, a dynamic one (segment ids) is a clause in
    ``keep`` under an unchanged grid; neither is a flag threaded beside
    ``causal``.

    ``causal``: ``k <= q``. ``window`` (causal only): besides, ``q - k <
    window``, a query sees itself and the ``window - 1`` keys before it.
    ``block_diffusion`` (causal only, no window beside it): the mask of
    training by diffusion over blocks (BD3-LM, arXiv:2503.09573). The
    sequence is two halves of ``half`` rows, the noised copy ``[0, half)``
    and the clean copy ``[half, 2 half)`` of the same ``half`` positions, in
    blocks of ``block_diffusion`` rows (a power of two that divides
    ``half``: the kernels shift and compare, they do not divide). A noised
    query sees its own noised block, both ways, and the clean blocks before
    its own; a clean query sees the clean blocks up to and including its
    own; no clean query sees a noised key. The kept set is not inside the
    causal triangle of the ``2 half`` square: the noised queries' tiles
    over the clean keys lie above its diagonal. Every row and column holds
    a kept pair (a row its own position), and rows past ``2 half`` (the
    padding) count as clean rows of blocks no real row has.
    Static: it rides the kernels' partial arguments and the custom VJP's
    non-differentiable ones."""
    causal: bool = True
    window: Optional[int] = None
    block_diffusion: Optional[int] = None
    half: Optional[int] = None

    def __post_init__(self):
        if self.window is not None and (not self.causal or self.window < 1):
            raise ValueError("a window is a causal band of at least one "
                             f"key, got {self!r}")
        block, half = self.block_diffusion, self.half
        if (block is None) != (half is None):
            raise ValueError("block_diffusion (the block) and half (the rows "
                             f"of each copy) go together, got {self!r}")
        if block is not None and (
                not self.causal or self.window is not None or block < 1
                or block & (block - 1) or half < 1 or half % block):
            raise ValueError(
                "block_diffusion is a block length, a power of two that "
                "divides each half of the sequence, beside neither a window "
                f"nor causal=False, got {self!r}")

    @property
    def name(self) -> str:
        return "block_diffusion" if self.block_diffusion is not None \
            else "window" if self.window is not None \
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
        if self.block_diffusion is not None:
            # Two compares on the pair, the rest on a row or a column of
            # positions (``_mask_tile`` hands this mask those, not tiles):
            # a noised key is kept by the noised queries of its block; a
            # clean key by the noised queries of later blocks and the clean
            # ones of its own and later.
            shift = self.block_diffusion.bit_length() - 1
            noised_q, noised_k = q_pos < self.half, k_pos < self.half
            q_block = jnp.where(noised_q, q_pos, q_pos - self.half) >> shift
            k_block = jnp.where(noised_k, k_pos, k_pos - self.half) >> shift
            own = jnp.where(noised_q, q_block, -2) \
                == jnp.where(noised_k, k_block, -1)
            return own | (jnp.where(noised_k, _NO_BLOCK, k_block)
                          <= jnp.where(noised_q, q_block - 1, q_block))
        keep = q_pos >= k_pos
        if self.window is not None:
            keep = keep & (q_pos - k_pos < self.window)
        return keep

    def _blocks_kept(self, q_first: int, q_last: int, k_first: int,
                     k_last: int) -> bool:
        """Block diffusion: whether queries ``[q_first, q_last]`` and keys
        ``[k_first, k_last]`` hold a kept pair. Python integers."""
        half, shift = self.half, self.block_diffusion.bit_length() - 1

        def blocks(first, last, clean):
            """The blocks of a range's noised or clean rows, or None."""
            first, last = (max(first, half) - half, last - half) if clean \
                else (first, min(last, half - 1))
            return None if last < max(first, 0) else (first >> shift,
                                                      last >> shift)

        q_noised, q_clean = (blocks(q_first, q_last, c) for c in (False, True))
        k_noised, k_clean = (blocks(k_first, k_last, c) for c in (False, True))
        return bool(
            (q_noised and k_noised and max(q_noised[0], k_noised[0])
             <= min(q_noised[1], k_noised[1]))
            or (q_noised and k_clean and k_clean[0] < q_noised[1])
            or (q_clean and k_clean and k_clean[0] <= q_clean[1]))

    def tile_kept(self, qi: int, kj: int, block_q: int, block_k: int) -> bool:
        """Whether the tile at block indices ``(qi, kj)`` holds any kept
        pair."""
        if not self.causal:
            return True
        if self.block_diffusion is not None:
            return self._blocks_kept(
                qi * block_q, (qi + 1) * block_q - 1,
                kj * block_k, (kj + 1) * block_k - 1)
        # The tile's last query row reaches its first key.
        kept = (qi + 1) * block_q - 1 >= kj * block_k
        if self.window is not None:
            # Its first query row still sees its last key.
            kept = kept and ((kj + 1) * block_k + self.window - 2
                             >= qi * block_q)
        return kept

    def kept_tiles(self, n_q: int, n_k: int, block_q: int, block_k: int,
                   by_column: bool = False) -> np.ndarray:
        """The tiles of an ``n_q x n_k`` grid that hold a kept pair, in the
        order a kernel walks them: the int32 table ``[4, T]`` its grid's
        one axis runs over, a step a column. Rows ``TILE_Q`` and ``TILE_K``
        are the step's q block and k block; ``TILE_FIRST`` and ``TILE_LAST``
        say whether it opens and closes its run, where the sums a kernel
        carries start and leave: a query block's tiles, keys ascending
        (row-major: the forward, dQ and the one backward kernel), or with
        ``by_column`` a key block's, queries ascending (the dKdV kernel of
        the pair). Trace time only (Python integers in, a NumPy table
        out). No row or column of these masks is empty: a row's output is
        written on its last tile."""
        tiles = [(i, j) for i in range(n_q) for j in range(n_k)
                 if self.tile_kept(i, j, block_q, block_k)]
        run = 1 if by_column else 0
        tiles.sort(key=lambda tile: (tile[run], tile[1 - run]))
        runs = [tile[run] for tile in tiles]
        first = [a != b for a, b in zip([None] + runs, runs)]
        return np.array([*zip(*tiles), first, first[1:] + [True]], np.int32)

    def tiles(self, n_q: int, n_k: int, block_q: int, block_k: int) -> dict:
        """How a ``n_q x n_k`` rectangle's tiles fall: ``kept`` (computed:
        the grid's steps), ``skipped`` (wholly above the diagonal) and
        ``skipped_band``
        (wholly below the band: what a window saves of the causal
        triangle's); under block diffusion ``kept`` and
        ``skipped_block_diffusion`` (every other tile of the rectangle,
        above its diagonal or below). Python integers, for the trace-time
        counter."""
        if self.block_diffusion is not None:
            kept = self.kept_tiles(n_q, n_k, block_q, block_k).shape[1]
            return {"kept": kept,
                    "skipped_block_diffusion": n_q * n_k - kept}
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

    def kept_pairs(self) -> Optional[int]:
        """The pairs the mask keeps, where the description alone says it:
        under block diffusion ``half ** 2 + half * block`` (clean-clean
        ``B^2 n (n + 1) / 2``, noised-clean ``B^2 n (n - 1) / 2``,
        noised-noised ``n B^2`` over ``n = half / B`` blocks); None for the
        masks whose count follows the call's length, which they do not
        hold. For the trace-time counter."""
        if self.block_diffusion is None:
            return None
        return self.half * (self.half + self.block_diffusion)


def _mask_tile(s, q_start, k_start, mask: Mask, kv_len: int,
               transposed: bool = False):
    """Mask a score tile whose first query row and key column are at global
    positions ``q_start``/``k_start``; ``transposed`` tiles are [keys,
    queries]."""
    q_dim, k_dim = (1, 0) if transposed else (0, 1)
    if mask.block_diffusion is not None:
        # A column of query positions against a row of key positions: what
        # ``keep`` derives from one position alone costs a vector's work,
        # not a tile's.
        along = {0: (s.shape[0], 1), 1: (1, s.shape[1])}
        return jnp.where(mask.keep(
            q_start + jax.lax.broadcasted_iota(jnp.int32, along[q_dim], q_dim),
            k_start + jax.lax.broadcasted_iota(jnp.int32, along[k_dim], k_dim),
            kv_len), s, NEG_INF)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_dim)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim) \
        if mask.causal else None
    return jnp.where(mask.keep(q_pos, k_pos, kv_len), s, NEG_INF)


def _fwd_kernel(tiles_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr,
                l_scr, acc_scr, *, tiles: np.ndarray, sm_scale: float,
                block_q: int, block_k: int, mask: Mask, kv_len: int,
                masked: bool):
    t = pl.program_id(1)
    qi, kj, first, last = _tile_at(tiles, tiles_ref, t)
    d = acc_scr.shape[-1]

    @pl.when(first == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @functools.partial(always, axis=1)
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

    @pl.when(last == 1)
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


def _dkdv_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, tiles: np.ndarray,
                 sm_scale: float, block_q: int, block_k: int, mask: Mask,
                 kv_len: int, masked: bool):
    t = pl.program_id(1)   # a tile of a k block's column, under a query head
    qi, kj, first, last = _tile_at(tiles, tiles_ref, t)

    @pl.when(first == 1)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @functools.partial(always, axis=1)
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

    @pl.when(last == 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, tiles: np.ndarray, sm_scale: float,
               block_q: int, block_k: int, mask: Mask, kv_len: int,
               masked: bool):
    t = pl.program_id(1)
    qi, kj, first, last = _tile_at(tiles, tiles_ref, t)

    @pl.when(first == 1)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @functools.partial(always, axis=1)
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

    @pl.when(last == 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                tiles: np.ndarray, group: int, sm_scale: float,
                block_q: int, block_k: int, mask: Mask, kv_len: int,
                masked: bool):
    """dQ, dK and dV from one score tile: the dKdV kernel's transposed tile
    and its walk over the group's query heads, in the dQ kernel's order. dK
    and dV of the K/V head's whole sequence are float32 sums in VMEM."""
    head, t = pl.program_id(1), pl.program_id(2)   # of the group; kept tile
    qi, kj, first, last = _tile_at(tiles, tiles_ref, t)

    @pl.when((head == 0) & (t == 0))
    def _init_head():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(first == 1)
    def _init_row():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    # On the tiles' axis, as in the other kernels: a sequence of one tile a
    # head makes it an axis of one, Mosaic folds the predicate and the step
    # is a straight line (under the heads' axis the one-tile control's call
    # ran 7% slower: PERF.md, Findings, PR 61).
    @functools.partial(always, axis=2)
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

    @pl.when(last == 1)
    def _finish_row():
        dq_ref[0] = (dq_scr[:].T * sm_scale).astype(dq_ref.dtype)

    @pl.when((head == group - 1) & (t == tiles.shape[1] - 1))
    def _finish_head():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pad_seq(x):
    """Pads the rows, an operand's last axis but one, to ``_PAD``."""
    pad = (-x.shape[-2]) % _PAD
    if pad:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0)))
    return x


def _dims(q, k, v):
    """``(B*H, B*Hkv, S, D, Dv, H, Hkv)`` of a call's operands, which come
    one of two ways. Rank 3, a head a row of the first axis: q
    ``[B*H, S, D]``, k ``[B*Hkv, S, D]``, v ``[B*Hkv, S, Dv]``, and ``H``
    and ``Hkv`` are None. Rank 4: q ``[B, H, S, D]``, k ``[B, Hkv, S, D]``,
    v ``[B, Hkv, S, Dv]``, the same bytes in the same order. What differs
    is what XLA sees round the call: a model's ``[B, S, H, D]`` turned to
    rank 4 is a transpose alone, which layout assignment folds into the
    layout of whatever wrote the array; merged to rank 3 it is a reshape
    too, and at a batch of two or more the layout does not cross it and the
    turn is a ``copy`` (PERF.md, Findings, PR 70). Outputs and cotangents
    come as their operands do; the row statistics are ``[B*H, ...]`` both
    ways."""
    if q.ndim == 3:
        return q.shape[0], k.shape[0], *q.shape[1:], v.shape[2], None, None
    b, h, s, d = q.shape
    return b * h, b * k.shape[1], s, d, v.shape[3], h, k.shape[1]


def _head_block(rows: int, width: int, heads: Optional[int], at):
    """The block of ``rows`` rows, ``width`` wide, of one head:
    ``at(*grid indices)`` gives ``(head, block)``, the head counted over
    ``B * heads`` as a grid's first axis counts it. ``heads`` None: of a
    rank-3 operand ``[B*heads, S, width]``; otherwise of ``[B, heads, S,
    width]``, the batch axis squeezed, so a kernel's ref is ``[1, rows,
    width]`` both ways."""
    if heads is None:
        return pl.BlockSpec((1, rows, width), lambda *g: (*at(*g), 0))

    def index(*g):
        head, block = at(*g)
        return _div(head, heads), _rem(head, heads), block, 0

    return pl.BlockSpec((None, 1, rows, width), index)


def _shape_of(n: int, s: int, d: int, heads: Optional[int]):
    """``n = B * heads`` heads of ``s`` rows of ``d`` as an output comes."""
    return (n, s, d) if heads is None else (n // heads, heads, s, d)


def _tiles_for(kernel, q, k, v, mask: Mask, forced, dq: str = "own",
               by_column: bool = False):
    """The call's tile, forced or from the table, and the kept tiles its
    grid walks (``Mask.kept_tiles``); and, trace time only, the record of
    them behind ``hvd.metrics()``: the tile (with the width of a query/key
    head and of a value head, and where dQ is made: ``fused`` on a dKdV
    kernel that makes it too, ``own`` on the pair's two, ``none`` on the
    forward), how the rectangle's tiles fall, and the steps a head's grid
    walks, which is the table's length."""
    bh, bkv, s, d, dv = _dims(q, k, v)[:5]
    bq, bk = forced or block_sizes(kernel, s, d, q.dtype, mask.causal, dv)
    n_q, n_k = s // bq, s // bk
    tiles = mask.kept_tiles(n_q, n_k, bq, bk, by_column)
    runtime.note_traced(
        "hvdtpu_spmd_flash_kernel_traces_total", kernel=kernel, block_q=bq,
        block_k=bk, operand_dtype=jnp.dtype(q.dtype).name,
        kv_group=bh // bkv, key_dim=d, value_dim=dv, dq=dq)
    for fall, n in mask.tiles(n_q, n_k, bq, bk).items():
        runtime.note_traced(
            "hvdtpu_spmd_flash_tiles_total", n, kernel=kernel,
            mask=mask.name, tiles=fall, seq=s)
    runtime.note_traced(
        "hvdtpu_spmd_flash_grid_steps_total", tiles.shape[1], kernel=kernel,
        mask=mask.name, seq=s)
    if mask.kept_pairs() is not None:
        for pairs, n in (("computed", tiles.shape[1] * bq * bk),
                         ("kept", mask.kept_pairs())):
            runtime.note_traced(
                "hvdtpu_spmd_flash_pairs_total", n, kernel=kernel,
                mask=mask.name, pairs=pairs, seq=s)
    return bq, bk, tiles


def _fwd_call(q, k, v, sm_scale, mask, kv_len, forced=None):
    """q: [B*H, S, D], k: [B*Hkv, S, D], v: [B*Hkv, S, Dv], or each at
    rank 4 (``_dims``; S already padded; ``kv_len`` is the real key count
    before padding; ``mask`` a :class:`Mask`). Returns (o, lse): o as q
    comes, ``Dv`` wide; lse lane-replicated [B*H, S, 128] either way."""
    bh, bkv, s, d, dv, h, hkv = _dims(q, k, v)
    group = bh // bkv
    bq, bk, tiles = _tiles_for(KERNEL_FWD, q, k, v, mask, forced, dq="none")

    def q_at(b, t, ref):
        return b, _at(tiles, ref, TILE_Q, t)

    def kv_at(b, t, ref):
        return _div(b, group), _at(tiles, ref, TILE_K, t)

    kernel = functools.partial(_fwd_kernel, tiles=tiles, sm_scale=sm_scale,
                               block_q=bq, block_k=bk, mask=mask,
                               kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, tiles.shape[1]),
            in_specs=[
                _head_block(bq, d, h, q_at),
                _head_block(bk, d, hkv, kv_at),
                _head_block(bk, dv, hkv, kv_at),
            ],
            out_specs=[
                _head_block(bq, dv, h, q_at),
                # lse rides lane-replicated [bh, s, 128] (see _fwd_kernel).
                _head_block(bq, LANES, None, q_at),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),   # running max
                pltpu.VMEM((bq, LANES), jnp.float32),   # running denominator
                pltpu.VMEM((bq, dv), jnp.float32),      # output accumulator
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(_shape_of(bh, s, dv, h), q.dtype,
                                 vma=_out_vma(q, k, v)),
            jax.ShapeDtypeStruct((bh, s, LANES), jnp.float32,
                                 vma=_out_vma(q, k, v)),
        ],
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=KERNEL_FWD,
    )(tiles, q, k, v)


def _over_group(tiles: np.ndarray, group: int) -> np.ndarray:
    """A column-major table as the dKdV kernel walks it: each k block's run
    once a query head of the group (row ``TILE_HEAD`` says which), the heads
    the outer order as in the one backward kernel, so a k block's sums run
    in that kernel's order; the run opens under the first head and closes
    under the last."""
    runs = np.split(tiles, np.flatnonzero(tiles[TILE_FIRST])[1:], axis=1)
    out = []
    for run in runs:
        for head in range(group):
            under = np.concatenate(
                [run, np.full((1, run.shape[1]), head, np.int32)])
            under[TILE_FIRST] &= head == 0
            under[TILE_LAST] &= head == group - 1
            out.append(under)
    return np.concatenate(out, axis=1)


def _dkdv_call(q, k, v, do, lse, delta, sm_scale, mask, kv_len,
               forced=None):
    """dK, dV at the K/V head count (``v``, ``do`` and dV as wide as a
    value head), at the rank of k and v (``_dims``). ``lse``/``delta``:
    [B*H, 1, S] rows either way."""
    bh, bkv, s, d, dv, h, hkv = _dims(q, k, v)
    group = bh // bkv
    bq, bk, tiles = _tiles_for(KERNEL_DKDV, q, k, v, mask, forced,
                               by_column=True)
    tiles = _over_group(tiles, group)

    def q_at(b, t, ref):
        return (b * group + _at(tiles, ref, TILE_HEAD, t),
                _at(tiles, ref, TILE_Q, t))

    def row_map(b, t, ref):
        head, i = q_at(b, t, ref)
        return head, 0, i

    def kv_at(b, t, ref):
        return b, _at(tiles, ref, TILE_K, t)

    kernel = functools.partial(_dkdv_kernel, tiles=tiles, sm_scale=sm_scale,
                               block_q=bq, block_k=bk, mask=mask,
                               kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    vma = _out_vma(q, k, v, do)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, tiles.shape[1]),
            in_specs=[
                _head_block(bq, d, h, q_at),                           # q
                _head_block(bk, d, hkv, kv_at),                        # k
                _head_block(bk, dv, hkv, kv_at),                       # v
                _head_block(bq, dv, h, q_at),                          # do
                pl.BlockSpec((1, 1, bq), row_map),                     # lse
                pl.BlockSpec((1, 1, bq), row_map),                     # delta
            ],
            out_specs=[
                _head_block(bk, d, hkv, kv_at),
                _head_block(bk, dv, hkv, kv_at),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(_shape_of(bkv, s, d, hkv), k.dtype, vma=vma),
            jax.ShapeDtypeStruct(_shape_of(bkv, s, dv, hkv), v.dtype,
                                 vma=vma),
        ],
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=KERNEL_DKDV,
    )(tiles, q, k, v, do, lse, delta)


def _dq_call(q, k, v, do, lse, delta, sm_scale, mask, kv_len, forced=None):
    """dQ (``v`` and ``do`` as wide as a value head), at q's rank
    (``_dims``). ``lse``/``delta``: lane-replicated [B*H, S, 128] either
    way."""
    bh, bkv, s, d, dv, h, hkv = _dims(q, k, v)
    group = bh // bkv
    bq, bk, tiles = _tiles_for(KERNEL_DQ, q, k, v, mask, forced)

    def q_at(b, t, ref):
        return b, _at(tiles, ref, TILE_Q, t)

    def kv_at(b, t, ref):
        return _div(b, group), _at(tiles, ref, TILE_K, t)

    kernel = functools.partial(_dq_kernel, tiles=tiles, sm_scale=sm_scale,
                               block_q=bq, block_k=bk, mask=mask,
                               kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, tiles.shape[1]),
            in_specs=[
                _head_block(bq, d, h, q_at),                           # q
                _head_block(bk, d, hkv, kv_at),                        # k
                _head_block(bk, dv, hkv, kv_at),                       # v
                _head_block(bq, dv, h, q_at),                          # do
                _head_block(bq, LANES, None, q_at),                    # lse
                _head_block(bq, LANES, None, q_at),                    # delta
            ],
            out_specs=_head_block(bq, d, h, q_at),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(_shape_of(bh, s, d, h), q.dtype,
                                       vma=_out_vma(q, k, v, do)),
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name=KERNEL_DQ,
    )(tiles, q, k, v, do, lse, delta)


def _bwd_call(q, k, v, do, lse, delta, sm_scale, mask, kv_len, forced=None):
    """dQ at the query head count, dK and dV at the K/V head count, from one
    kernel (``hvd_flash_dkdv`` in the compiled program: what the
    benchmark's readers of the backward pass match), each at its operand's
    rank (``_dims``). ``lse``/``delta``: [B*H, 1, S] rows either way."""
    bh, bkv, s, d, dv, h, hkv = _dims(q, k, v)
    group = bh // bkv
    bq, bk, tiles = _tiles_for(KERNEL_DKDV, q, k, v, mask, forced,
                               dq="fused")

    def q_at(b, head, t, ref):
        return b * group + head, _at(tiles, ref, TILE_Q, t)

    def row_map(b, head, t, ref):
        return b * group + head, 0, _at(tiles, ref, TILE_Q, t)

    def kv_at(b, head, t, ref):
        return b, _at(tiles, ref, TILE_K, t)

    def whole_at(b, head, t, ref):
        return b, 0

    kernel = functools.partial(_bwd_kernel, tiles=tiles, group=group,
                               sm_scale=sm_scale, block_q=bq, block_k=bk,
                               mask=mask, kv_len=kv_len,
                               masked=mask.needs_masking(kv_len, s))
    vma = _out_vma(q, k, v, do)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # A K/V head; the query head of its group; the head's kept tile.
            # (The last two as one axis of group x tiles cost a one-tile
            # sequence's call 7%: PERF.md, Findings, PR 61.)
            grid=(bkv, group, tiles.shape[1]),
            in_specs=[
                _head_block(bq, d, h, q_at),                           # q
                _head_block(bk, d, hkv, kv_at),                        # k
                _head_block(bk, dv, hkv, kv_at),                       # v
                _head_block(bq, dv, h, q_at),                          # do
                pl.BlockSpec((1, 1, bq), row_map),                     # lse
                pl.BlockSpec((1, 1, bq), row_map),                     # delta
            ],
            out_specs=[
                _head_block(bq, d, h, q_at),
                # A K/V head's whole dK and dV: the index is constant over
                # the head's steps, so they leave VMEM once, on its last.
                _head_block(s, d, hkv, whole_at),
                _head_block(s, dv, hkv, whole_at),
            ],
            scratch_shapes=[
                pltpu.VMEM((d, bq), jnp.float32),   # dQ of the row, transposed
                pltpu.VMEM((s, d), jnp.float32),    # the K/V head's dK
                pltpu.VMEM((s, dv), jnp.float32),   # ... and dV
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(_shape_of(bh, s, d, h), q.dtype, vma=vma),
            jax.ShapeDtypeStruct(_shape_of(bkv, s, d, hkv), k.dtype, vma=vma),
            jax.ShapeDtypeStruct(_shape_of(bkv, s, dv, hkv), v.dtype,
                                 vma=vma),
        ],
        compiler_params=_compiler_params(rank=3),
        interpret=_use_interpret(),
        name=KERNEL_DKDV,
    )(tiles, q, k, v, do, lse, delta)


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
    bh, _, s, d, dv = _dims(q, k, v)[:5]
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(bh, s)   # at rank 4 [B, H, S]: floats, as they lie
    # The statistics enter a transposed tile as [bh, 1, s] rows.
    rows = lse[:, None, :], delta[:, None, :]
    tile = forced or block_sizes(KERNEL_DKDV, s, d, q.dtype, mask.causal, dv)
    if backward_is_fused(*tile, s, d, q.dtype, dv):
        return _bwd_call(q, k, v, do, *rows, sm_scale, mask, kv_len, tile)
    # Too long for a head's dK and dV to stay in VMEM: a kernel each. dQ
    # reads the statistics lane-replicated [bh, s, 128] (Mosaic rejects
    # vector blocks whose sublane dim is 1 — see _fwd_kernel), transiently:
    # the residual holds one float a row.
    dk, dv_ = _dkdv_call(q, k, v, do, *rows, sm_scale, mask, kv_len, forced)
    dq = _dq_call(q, k, v, do,
                  jnp.broadcast_to(lse[..., None], (bh, s, LANES)),
                  jnp.broadcast_to(delta[..., None], (bh, s, LANES)),
                  sm_scale, mask, kv_len, forced)
    return dq, dk, dv_


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(q, k, v, causal: bool = True, *,
                    window: Optional[int] = None,
                    block_diffusion: Optional[int] = None,
                    heads_major: bool = False, _blocks=None):
    """Fused attention. q: ``[B, S, H, D]`` (the layout the GPT blocks
    use); k/v: ``[B, S, Hkv, D]`` where ``Hkv`` may divide ``H``
    (grouped-query attention: the kernels read K/V head ``h // group`` for
    query head ``h``, nothing is repeated, and dK/dV come back at ``Hkv``
    heads); v may be ``[B, S, Hkv, Dv]`` with ``Dv != D``, and the output
    is then ``[B, S, H, Dv]`` (the scores are over ``D`` and scaled by one
    over its root). Differentiable (custom VJP, flash backward).

    ``causal=True`` (decoder) walks no tile above the diagonal;
    ``causal=False`` (encoder/bidirectional) computes all blocks with the
    tail padding masked out of the key axis. ``window`` (static, causal
    only): a query sees itself and the ``window - 1`` keys before it, and
    the tiles wholly below that band are no grid steps either, as those
    above the diagonal are not, in every kernel; a
    window of the sequence's length or more is the causal program, unchanged
    (:class:`Mask`). ``block_diffusion`` (static, causal only, no window
    beside it): the sequence is the noised and the clean copy of ``S / 2``
    positions, one after the other, under the block-diffusion mask at that
    block length (:class:`Mask`), and the grids walk the tiles that hold a
    kept pair of it. Tile sizes and the MXU operands' dtype follow the
    call's shapes and dtype (``block_sizes``); ``_blocks=(block_q,
    block_k)`` forces one tile on every kernel, for the tests and the
    sweep.

    ``heads_major`` is the caller's, because the layouts round the call are
    the caller's: the kernels read ``[B, H, S, D]``, and the turn from the
    model's ``[B, S, H, D]`` is either merged to ``[B*H, S, D]`` here (the
    default, every call until PR 70), which at a batch of two or more XLA
    cannot carry a layout across, so every operand, output and cotangent is
    turned in a ``copy`` of its own; or, ``heads_major=True``, left a
    transpose at rank 4 (``_dims``), which XLA folds into whatever wrote the
    array and whatever reads the output. Folded, the turn does not vanish:
    it moves into those neighbours, cheap in an elementwise fusion and dear
    in a product that contracts over ``(h, d)`` with ``s`` between them in
    memory, so a caller asks for it knowing its neighbours: the attention
    mixer at heads of one lane tile, the latent-attention mixer at its 192
    beside 128, each at a batch of two or more
    (``models/decoder/mixers/attention.py``, ``mixers/mla.py``; PERF.md,
    Findings, PR 70 and PR 72). The values, the kernels' bodies, grids and
    names are the same both ways.
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
    if block_diffusion is not None:
        if window is not None or s % 2:
            raise ValueError(
                f"block_diffusion={block_diffusion} takes a sequence of two "
                f"halves and no window, got {s} rows, window={window}")
        mask = Mask(bool(causal), None, int(block_diffusion), s // 2)
    else:
        mask = Mask(bool(causal),
                    None if window is None or window >= s else int(window))

    dv = v.shape[3]
    runtime.note_traced(
        "hvdtpu_spmd_flash_layout_traces_total",
        layout="rank4" if heads_major else "rank3", head_dim=d, batch=b)

    def to_bhsd(x):
        x = x.transpose(0, 2, 1, 3)
        return _pad_seq(x if heads_major else x.reshape(-1, s, x.shape[3]))

    o = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), sm_scale, mask, s,
                    _blocks)
    return o[..., :s, :].reshape(b, h, s, dv).transpose(0, 2, 1, 3)
