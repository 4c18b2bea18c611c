"""The state-space layer's mathematics (Mamba-2's "state-space duality"; Dao
and Gu, arXiv:2405.21060): the chunked scan and the plain recurrence the
tests hold it to, as :func:`horovod_tpu.ops.attention.default_attention` is
for the flash kernels (the causal depthwise convolution in front of the scan
is ``ops/conv.py``'s). The within-chunk term is two Pallas kernels (below),
which also add the entering state's part and the skip where ``y`` is
written; everything else is ``jax.numpy`` that XLA lowers.

For one head (``x_t`` in ``R^P``) of group ``g`` (``B_t``, ``C_t`` in
``R^N``, shared by the group's heads), state ``S`` in ``R^{P x N}``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T ;    y_t = S_t C_t + D x_t

:func:`ssd_chunked` computes it a chunk of ``Q`` tokens at a time. With
``a_t = dt_t A`` and ``cum`` its running sum inside a chunk:

* inside a chunk, ``y_i += sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j
  x_j``: the masked, decay-weighted ``C B^T`` product applied to ``x``;
* a chunk's own state, ``sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T``;
* the short recurrence over chunks, ``S_in' = exp(cum_Q) S_in + own``;
* the entering state's part, ``y_i += exp(cum_i) S_in C_i``.

``dt``, ``A``, the running sums and every exponential are float32 (a decay
over 256 tokens is a product of 256 factors: in bfloat16 the running sum
alone would be off by several percent); the four products run on the MXU in
``dtype`` with float32 accumulation. A length the chunk does not divide is
padded with ``dt = 0`` rows, which neither decay the state nor add to it.

**The kernels** (``hvd_ssd_fwd``, ``hvd_ssd_bwd``, one ``jax.custom_vjp``:
:func:`_scan_output`) compute the first term, the only one with two
chunk-length axes: the decay tile ``exp(cum_i - cum_j)`` of every head, its
product with ``C B^T`` and their cotangents live in VMEM and never reach
HBM. They work with tokens on the lanes (``x`` as ``[B, H P, S]``), the
layout XLA gives the mixer's activations around the scan on the TPU, so
neither side copies: ``y^T = (dt x)^T Wt`` with ``Wt[j, i] = exp(cum_i -
cum_j) (C_i . B_j)``. A grid cell is one chunk of one sequence and a block of
heads of one group, walked in a loop; ``(C B^T)^T`` is made once a group and
held in scratch while the grid walks the group's head blocks. A chunk is cut into 128 x 128
tiles and the tiles with ``i < j`` throughout are skipped. Where ``y`` is
written the forward kernel also multiplies ``x`` by ``dt``, adds the entering
state's part (XLA's product ``S_in C_i``, taken in the order the product
leaves it, under ``exp(cum_i)``) and ``D x``, and rounds once to ``dtype``;
the products of the other three terms and the recurrence stay XLA's. The
backward kernel remakes ``(C B^T)^T`` and each decay tile from the inputs,
sums ``d(C B^T)`` over a group's heads in its output block, returns ``d cum``
as its two float32 halves (``sum_j (dW W)_ij`` as rows, ``sum_j (dW W)_ji``
as columns) and the elementwise terms' cotangents. The residuals are the
kernels' inputs, so a checkpointed block that keeps the scan's output never
runs the forward kernel twice. Off the TPU the kernels run in Pallas
interpret mode; on it a shape they do not tile raises (:func:`_tiling`).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .pallas_util import LANES, NEG_INF, NT, SUBLANES, TN, always, \
    div as _div, largest_divisor, out_vma as _out_vma, rem as _rem, \
    use_interpret as _use_interpret, varying_like

# The kernels' names in the compiled program and in a device trace; the
# benchmark's readers match ``^hvd_ssd_`` (tests/test_program_names.py).
KERNEL_FWD = "hvd_ssd_fwd"
KERNEL_BWD = "hvd_ssd_bwd"
_TILE = LANES     # a chunk is cut into tiles of this side (the lane width)
_MAX_HEADS = 16   # heads a grid cell, at most


def _by_group(t, groups: int, axis: int):
    """The heads on ``axis`` split into ``[G, H / G]``."""
    return t.reshape(t.shape[:axis] + (groups, t.shape[axis] // groups)
                     + t.shape[axis + 1:])


def heads_per_block(heads_per_group: int) -> int:
    """Heads a grid cell of the kernels holds: the largest divisor of a
    group's heads, ``_MAX_HEADS`` at most."""
    return largest_divisor(heads_per_group, _MAX_HEADS)


def _tiling(kernel, x, b_in, chunk):
    """``(heads_per_block, tile)`` of a call on ``x`` ``[B, S, H, P]`` and
    ``b_in`` ``[B, S, G, N]``; and, trace time only, the record of it behind
    ``hvd.metrics()``. Compiled for the TPU, a shape the kernels do not tile
    raises here, by name."""
    heads, width = x.shape[2:]
    groups, state = b_in.shape[2:]
    hb = heads_per_block(heads // groups)
    tile = _TILE if chunk % _TILE == 0 else chunk
    if not _use_interpret() and (
            tile != _TILE or width % SUBLANES
            or (groups > 1 and state % SUBLANES)):
        raise ValueError(
            f"{kernel} does not tile chunk={chunk}, head_dim={width}, "
            f"state={state} in {groups} groups: it needs a chunk that is a "
            f"multiple of {_TILE}, a head_dim that is a multiple of "
            f"{SUBLANES} and, with several groups, such a state")
    runtime.note_traced(
        "hvdtpu_spmd_ssd_kernel_traces_total", kernel=kernel, chunk=chunk,
        heads_per_block=hb, operand_dtype=jnp.dtype(x.dtype).name)
    return hb, tile


def _decay_t(cum_i, cum_j, keep):
    """The float32 tile ``exp(cum_i - cum_j)`` ``[j, i]`` of a row ``[1,
    T]`` and a column ``[T, 1]``, kept where ``i >= j``. The mask goes on
    the exponent: on the masked side the difference is positive and may
    overflow."""
    exponent = cum_i - cum_j
    if keep is not None:
        exponent = jnp.where(keep, exponent, NEG_INF)
    return jnp.exp(exponent)


def _keep_t(tile: int):
    """``[tile, tile]`` bool, ``[j, i]``: ``i >= j``."""
    return lax.broadcasted_iota(jnp.int32, (tile, tile), 1) \
        >= lax.broadcasted_iota(jnp.int32, (tile, tile), 0)


def _group_product(b_ref, c_ref, cbt_scr, blocks_per_group: int, *also):
    """At a group's first head block: ``(C B^T)^T`` ``[j, i]`` into the
    scratch, where the group's other blocks find it; ``also`` run then
    too."""
    @pl.when(_rem(pl.program_id(2), blocks_per_group) == 0)
    def _group():
        cbt_scr[:] = lax.dot_general(b_ref[0], c_ref[0], TN,
                                     preferred_element_type=jnp.float32)
        for f in also:
            f()


def _sum_rows(t):
    """A tile summed over its rows (a head's channels; ``j``): ``[1, T]``."""
    return jnp.sum(t, axis=0, keepdims=True)


def _row(block, h):
    """Row ``h`` (a loop index) of a ``[hb, T]`` value, ``[1, T]``: Mosaic
    loads no single row at an index it cannot see."""
    picked = lax.broadcasted_iota(jnp.int32, block.shape, 0) == h
    return _sum_rows(jnp.where(picked, block, 0.0))


def _put_row(block, h, row):
    """``block`` ``[hb, T]`` with ``row`` ``[1, T]`` at row ``h``."""
    return jnp.where(lax.broadcasted_iota(jnp.int32, block.shape, 0) == h,
                     row, block)


def _head_loop(cum_c_ref, col_scr, hb: int, width: int, body, carry):
    """``body(rows, col, h, carry) -> carry`` for each head of the grid
    cell, in a loop and not unrolled: what JAX traces and Mosaic compiles is
    one head's work, whatever ``hb`` (all 16 unrolled ran 1% faster and cost
    a job 2.5 s more of set-up, four a turn 2% faster for 1 s: PERF.md,
    Findings, PR 30). ``rows`` are the head's channels, ``col``
    its running sums as columns, lane-replicated ``[Q, T]`` (made here for
    all heads, since a loop index cannot pick a lane)."""
    for h in range(hb):
        col_scr[h] = jnp.broadcast_to(cum_c_ref[0, 0, 0, :, h:h + 1],
                                      col_scr.shape[1:])

    def head(h, carry):
        rows = pl.ds(pl.multiple_of(h * width, width), width)
        return body(rows, col_scr[h], h, carry)

    return lax.fori_loop(0, hb, head, carry)


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, cum_r_ref, cum_c_ref, thr_ref,
                d_ref, y_ref, cbt_scr, col_scr, *, hb: int, width: int,
                tile: int, blocks_per_group: int):
    """``y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j +
    exp(cum_i) through_i + D x_i`` for the ``hb`` heads of a grid cell,
    tokens on the lanes: the first term is ``(dt x)^T Wt`` with ``Wt`` ``[j,
    i]``. ``cum`` comes twice, as rows ``[hb, Q]`` and as columns ``[Q,
    hb]``."""
    _group_product(b_ref, c_ref, cbt_scr, blocks_per_group)
    n_tiles = cbt_scr.shape[0] // tile
    at = [slice(t * tile, (t + 1) * tile) for t in range(n_tiles)]

    @always
    def _heads():
        keep = _keep_t(tile)

        def head(rows, col, h, carry):
            x = [x_ref[0, rows, at_t].astype(jnp.float32) for at_t in at]
            xdt = [(x_t * _row(dt_ref[0, 0, :, at_t], h)).astype(x_ref.dtype)
                   for x_t, at_t in zip(x, at)]
            for i in range(n_tiles):
                cum_i = _row(cum_r_ref[0, 0, :, at[i]], h)
                acc = thr_ref[0, 0, rows, at[i]] * jnp.exp(cum_i) \
                    + d_ref[0, rows, :] * x[i]
                for j in range(i + 1):  # tiles above the diagonal: skipped
                    wt = _decay_t(cum_i, col[at[j]],
                                  keep if i == j else None) \
                        * cbt_scr[at[j], at[i]]
                    acc += jnp.dot(xdt[j], wt.astype(x_ref.dtype),
                                   preferred_element_type=jnp.float32)
                y_ref[0, rows, at[i]] = acc.astype(y_ref.dtype)
            return carry

        _head_loop(cum_c_ref, col_scr, hb, width, head, 0)


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, cum_r_ref, cum_c_ref, thr_ref,
                d_ref, dy_ref, dx_ref, ddt_ref, dcb_ref, dcum_r_ref,
                dcum_c_ref, dthr_ref, dd_ref, cbt_scr, col_scr, *, hb: int,
                width: int, tile: int, blocks_per_group: int):
    """The forward's cotangents on the same tile ``[j, i]``: ``d(dt x)^T =
    dy^T Wt^T``; ``dWt = (dt x) dy^T``; ``d(C B^T)`` summed over the group's
    heads in the output block; ``M = dWt * Wt`` summed down its columns
    (over ``j``, for ``d cum_i``) and along its rows (over ``i``, against
    ``d cum_j``); and the elementwise terms' own: ``d through = exp(cum)
    dy``, its part of ``d cum``, ``D dy`` in ``dx``, ``dt``'s and (a row a
    head, summed outside) ``D``'s."""
    def _zero():
        dcb_ref[0, 0, 0] = jnp.zeros(dcb_ref.shape[3:], jnp.float32)

    _group_product(b_ref, c_ref, cbt_scr, blocks_per_group, _zero)
    chunk = cbt_scr.shape[0]
    n_tiles = chunk // tile
    at = [slice(t * tile, (t + 1) * tile) for t in range(n_tiles)]

    @always
    def _heads():
        keep = _keep_t(tile)
        head_lane = lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)

        def head(rows, col, h, carry):
            along, down_all, ddt_all, dd_all = carry
            skip = d_ref[0, rows, :]
            dy = [dy_ref[0, rows, at_t] for at_t in at]
            cum = [_row(cum_r_ref[0, 0, :, at_t], h) for at_t in at]
            down, dd = [], []
            for i in range(n_tiles):
                x_i = x_ref[0, rows, at[i]].astype(jnp.float32)
                dy_i = dy[i].astype(jnp.float32)
                dthr = dy_i * jnp.exp(cum[i])
                dthr_ref[0, 0, rows, at[i]] = dthr
                down.append(_sum_rows(dthr * thr_ref[0, 0, rows, at[i]]))
                dd.append(_sum_rows(dy_i * x_i))
            along_h, ddt = [], []
            for j in range(n_tiles):
                x = x_ref[0, rows, at[j]].astype(jnp.float32)
                dt = _row(dt_ref[0, 0, :, at[j]], h)
                xdt = (x * dt).astype(x_ref.dtype)
                dxdt = m_rows = None
                for i in range(j, n_tiles):  # under the diagonal: skipped
                    cbt = cbt_scr[at[j], at[i]]
                    decay = _decay_t(cum[i], col[at[j]],
                                     keep if i == j else None)
                    part = lax.dot_general(
                        dy[i], (decay * cbt).astype(dy[i].dtype), NT,
                        preferred_element_type=jnp.float32)
                    dxdt = part if dxdt is None else dxdt + part
                    dcb = decay * lax.dot_general(
                        xdt, dy[i], TN, preferred_element_type=jnp.float32)
                    dcb_ref[0, 0, 0, at[j], at[i]] += dcb
                    m = dcb * cbt
                    m_rows = m if m_rows is None else m_rows + m
                    down[i] += _sum_rows(m)
                dx_ref[0, rows, at[j]] = (
                    dxdt * dt + skip * dy[j].astype(jnp.float32)
                ).astype(dx_ref.dtype)
                ddt.append(_sum_rows(dxdt * x))
                along_h.append(jnp.sum(m_rows, axis=1, keepdims=True))

            def put(block, tiles):
                return _put_row(block, h, jnp.concatenate(tiles, axis=1))

            return (jnp.where(head_lane == h,
                              jnp.concatenate(along_h, axis=0), along),
                    put(down_all, down), put(ddt_all, ddt), put(dd_all, dd))

        rows_0 = jnp.zeros((hb, chunk), jnp.float32)
        dcum_c_ref[0, 0, 0], dcum_r_ref[0, 0], ddt_ref[0, 0], dd_ref[0, 0] \
            = _head_loop(
                cum_c_ref, col_scr, hb, width, head,
                (jnp.zeros((chunk, hb), jnp.float32),) + 3 * (rows_0,))


def _specs(chunk, hb, width, groups, state, n_blocks):
    """Block specs on the grid ``(batch, chunk, head block)``, tokens on the
    lanes: ``heads`` for ``[B, H P, S]`` (the block's heads, the chunk's
    tokens), ``group`` for ``[B, G N, S]`` (``B`` or ``C`` of the block's
    group), ``rows`` for a float32 a head a token ``[B, H / hb, hb, S]``,
    ``cols`` for ``cum`` as columns ``[B, c, H / hb, Q, hb]``, ``through``
    for ``[B, c, H P, Q]``, ``pair`` for ``[B, c, G, Q, Q]``, ``skip`` for
    ``D`` a channel ``[H / hb, hb P, 1]``. Heads of a group are contiguous,
    so a group is ``n_blocks / groups`` consecutive blocks."""
    per_group = n_blocks // groups

    def group_of(k):
        return _div(k, per_group)

    return {
        "heads": pl.BlockSpec((1, hb * width, chunk),
                              lambda b, c, k: (b, k, c)),
        "group": pl.BlockSpec((1, state, chunk),
                              lambda b, c, k: (b, group_of(k), c)),
        "rows": pl.BlockSpec((1, 1, hb, chunk),
                             lambda b, c, k: (b, k, 0, c)),
        "cols": pl.BlockSpec((1, 1, 1, chunk, hb),
                             lambda b, c, k: (b, c, k, 0, 0)),
        "through": pl.BlockSpec((1, 1, hb * width, chunk),
                                lambda b, c, k: (b, c, k, 0)),
        "pair": pl.BlockSpec((1, 1, 1, chunk, chunk),
                             lambda b, c, k: (b, c, group_of(k), 0, 0)),
        "skip": pl.BlockSpec((1, hb * width, 1), lambda b, c, k: (k, 0, 0)),
    }, per_group


def _tokens_last(t):
    """``[B, S, ...]`` -> ``[B, prod(...), S]``: channels before tokens, as
    XLA lays the mixer's activations out around the scan on the TPU (the
    convolution and the projections leave them so), where this is no copy."""
    return t.reshape(t.shape[:2] + (-1,)).swapaxes(1, 2)


def _tokens_first(t, like):
    """The inverse of :func:`_tokens_last`, to ``like``'s shape."""
    return t.reshape(like.shape[:1] + (-1, like.shape[1])).swapaxes(1, 2) \
        .reshape(like.shape)


def _kernel_layout(x, dt, b_in, c_in, cum, through, d, hb):
    """The kernels' views, tokens on the lanes: ``x`` ``[B, H P, S]``;
    ``dt``, ``cum`` ``[B, H / hb, hb, S]``; ``B``, ``C`` ``[B, G N, S]``;
    ``cum`` again as columns ``[B, c, H / hb, Q, hb]``; ``through`` ``[B, c,
    H P, Q]``; ``D`` a channel ``[H / hb, hb P, 1]``."""
    batch, tokens, heads, width = x.shape
    n_chunks, chunk = through.shape[1], through.shape[-1]
    rows = (batch, heads // hb, hb, tokens)
    return (_tokens_last(x), _tokens_last(dt).reshape(rows),
            _tokens_last(b_in), _tokens_last(c_in),
            _tokens_last(cum).reshape(rows),
            cum.reshape(batch, n_chunks, chunk, heads // hb, hb)
            .swapaxes(2, 3),
            through.reshape(batch, n_chunks, -1, chunk),
            jnp.repeat(d, width).reshape(heads // hb, hb * width, 1))


_FWD_SPECS = ("heads", "rows", "group", "group", "rows", "cols", "through",
              "skip")


def _plan(kernel, body, x, dt, b_in, c_in, cum, through, d):
    """What both calls share: the operands laid out for the kernels, the
    block specs by name, and ``pallas_call``'s other arguments (the grid,
    the kernel ``body`` with its tiling bound, the scratch for ``(C
    B^T)^T`` and for the heads' running sums as columns)."""
    batch, _, heads, width = x.shape
    groups, state = b_in.shape[2:]
    n_chunks, chunk = through.shape[1], through.shape[-1]
    hb, tile = _tiling(kernel, x, b_in, chunk)
    specs, per_group = _specs(chunk, hb, width, groups, state, heads // hb)
    call = dict(
        grid=(batch, n_chunks, heads // hb),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((hb, chunk, tile), jnp.float32)],
        # The head blocks of a group run in order: they share the scratch's
        # product and sum into one d(C B^T) block.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(), name=kernel)
    body = functools.partial(body, hb=hb, width=width, tile=tile,
                             blocks_per_group=per_group)
    return _kernel_layout(x, dt, b_in, c_in, cum, through, d, hb), specs, \
        body, call


def _fwd_call(x, dt, b_in, c_in, cum, through, d):
    """``x`` ``[B, S, H, P]`` and ``b_in``, ``c_in`` ``[B, S, G, N]`` in the
    operand dtype; float32 ``dt`` and ``cum`` ``[B, S, H]`` (the running sum
    inside each chunk), ``through`` ``[B, c, H, P, Q]`` (the entering state
    through ``C``, before its decay) and ``d`` ``[H]`` -> the scan's output
    ``y`` ``[B, S, H, P]`` in the operand dtype."""
    args, specs, body, call = _plan(KERNEL_FWD, _fwd_kernel, x, dt, b_in,
                                    c_in, cum, through, d)
    y = pl.pallas_call(
        body, in_specs=[specs[name] for name in _FWD_SPECS],
        out_specs=specs["heads"],
        out_shape=jax.ShapeDtypeStruct(args[0].shape, x.dtype,
                                       vma=_out_vma(*args)),
        **call)(*args)
    return _tokens_first(y, x)


def _bwd_call(x, dt, b_in, c_in, cum, through, d, dy):
    """The cotangents of :func:`_fwd_call`'s inputs for ``dy`` ``[B, S, H,
    P]`` in the operand dtype: ``dx`` (operand dtype), ``d dt``, ``d(C
    B^T)`` ``[B, c, G, Q_j, Q_i]``, ``d cum``, ``d through``, ``d D``, all
    float32."""
    args, specs, body, call = _plan(KERNEL_BWD, _bwd_kernel, x, dt, b_in,
                                    c_in, cum, through, d)
    args += (_tokens_last(dy),)
    vma = _out_vma(*args)
    chunk = through.shape[-1]

    def like(t, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(t.shape, dtype, vma=vma)

    dx, ddt, dcbt, dcum_r, dcum_c, dthr, dd = pl.pallas_call(
        body, in_specs=[specs[name] for name in _FWD_SPECS + ("heads",)],
        out_specs=[specs["heads"], specs["rows"], specs["pair"],
                   specs["rows"], specs["cols"], specs["through"],
                   specs["rows"]],
        out_shape=[
            like(args[0], x.dtype), like(args[1]),
            jax.ShapeDtypeStruct(
                through.shape[:2] + (b_in.shape[2], chunk, chunk),
                jnp.float32, vma=vma),
            like(args[4]), like(args[5]), like(args[6]), like(args[1])],
        **call)(*args)
    dcum = _tokens_first(dcum_r, cum) \
        - dcum_c.swapaxes(2, 3).reshape(cum.shape)
    return (_tokens_first(dx, x), _tokens_first(ddt, dt), dcbt, dcum,
            dthr.reshape(through.shape), jnp.sum(dd, axis=(0, 3)).reshape(-1))


@jax.custom_vjp
def _scan_output(x, dt, b_in, c_in, cum, through, d):
    """The scan's output from its parts, through the kernels: see
    :func:`_fwd_call`."""
    return _fwd_call(x, dt, b_in, c_in, cum, through, d)


def _scan_output_fwd(*inputs):
    # The residuals are the inputs alone: a checkpointed caller that keeps
    # the scan's output has no use for this kernel in its recomputed copy.
    return _fwd_call(*inputs), inputs


def _scan_output_bwd(inputs, dy):
    x, _, b_in, c_in = inputs[:4]
    n_chunks, chunk = inputs[5].shape[1], inputs[5].shape[-1]
    dx, ddt, dcbt, dcum, dthr, dd = _bwd_call(*inputs, dy.astype(x.dtype))

    def by_chunk(t):
        return t.reshape(t.shape[:1] + (n_chunks, chunk) + t.shape[2:])

    # d(C B^T) is [j, i]: dB_j = sum_i dcbt_ji C_i, dC_i = sum_j dcbt_ji B_j.
    dcbt = dcbt.astype(x.dtype)
    db = jnp.einsum("bcgji,bcign->bcjgn", dcbt, by_chunk(c_in),
                    preferred_element_type=jnp.float32)
    dc = jnp.einsum("bcgji,bcjgn->bcign", dcbt, by_chunk(b_in),
                    preferred_element_type=jnp.float32)
    return (dx, ddt, db.reshape(b_in.shape).astype(b_in.dtype),
            dc.reshape(c_in.shape).astype(c_in.dtype), dcum, dthr, dd)


_scan_output.defvjp(_scan_output_fwd, _scan_output_bwd)


def ssd_chunked(x, dt, a, b_in, c_in, d, *, chunk: int,
                dtype: Any = jnp.bfloat16, initial_state=None):
    """The recurrence above in chunks of ``chunk`` tokens.

    Args:
      x: ``[B, S, H, P]``.
      dt: ``[B, S, H]`` float32 step sizes (after the soft-plus), >= 0.
      a: ``[H]`` float32, negative.
      b_in, c_in: ``[B, S, G, N]``, ``G`` dividing ``H``.
      d: ``[H]``, the skip.
      chunk: tokens a chunk.
      dtype: the MXU operands' type.
      initial_state: ``[B, H, P, N]`` float32, zeros if None.

    Returns ``(y, state)``: ``y`` ``[B, S, H, P]`` in ``dtype`` and the
    float32 state after the last token ``[B, H, P, N]``.
    """
    batch, seq, heads, width = x.shape
    groups, state = b_in.shape[2], b_in.shape[3]
    if heads % groups:
        raise ValueError(f"heads ({heads}) not a multiple of groups "
                         f"({groups})")
    runtime.note_traced(
        "hvdtpu_spmd_ssm_layer_traces_total", heads=heads, head_dim=width,
        state=state, groups=groups, chunk=chunk)

    f32 = jnp.float32
    pad = (-seq) % chunk
    if pad:
        # dt = 0: exp(0 A) = 1 keeps the state, dt x B^T = 0 adds nothing.
        x, dt, b_in, c_in = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_in, c_in))
    n_chunks = (seq + pad) // chunk

    def chunked(t):
        return t.reshape((batch, n_chunks, chunk) + t.shape[2:])

    dt = dt.astype(f32)
    b_in, c_in = b_in.astype(dtype), c_in.astype(dtype)
    dtc = chunked(dt)                                       # [B, c, Q, H]
    # Running sums of a_t = dt_t A inside a chunk.
    cum = jnp.cumsum(dtc * a.astype(f32), axis=2)
    last = cum[:, :, -1]                                    # [B, c, H]

    # A chunk's own state: what its tokens leave at its end.
    to_end = dtc * jnp.exp(last[:, :, None] - cum)
    own = jnp.einsum(
        "bcjgkp,bcjgn->bcgkpn",
        _by_group((chunked(x).astype(f32) * to_end[..., None]).astype(dtype),
                  groups, 3), chunked(b_in), preferred_element_type=f32)
    own = own.reshape(batch, n_chunks, heads, width, state)

    # The short recurrence over chunks, float32 and elementwise.
    def carry_on(entering, now):
        own_c, decay_c = now
        return decay_c[..., None, None] * entering + own_c, entering

    start = jnp.zeros((batch, heads, width, state), f32) \
        if initial_state is None else initial_state.astype(f32)
    final, entering = lax.scan(
        carry_on, varying_like(start, own),
        (own.swapaxes(0, 1), jnp.exp(last).swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                      # [B, c, H, P, N]

    # What the entering state gives each token through C, before its decay.
    through = jnp.einsum(
        "bcign,bcgkpn->bcgkpi", chunked(c_in),
        _by_group(entering.astype(dtype), groups, 2),
        preferred_element_type=f32)
    # The kernels: inside a chunk, (C B^T, masked and weighted by the decay)
    # applied to dt x, no [Q, Q] tile of it in HBM; the entering state's part
    # under its decay and the skip added there, where y is written.
    y = _scan_output(x.astype(dtype), dt, b_in, c_in, cum.reshape(dt.shape),
                     through.reshape(batch, n_chunks, heads, width, chunk),
                     varying_like(d.astype(f32), x))
    return y[:, :seq], final


def ssd_sequential(x, dt, a, b_in, c_in, d, initial_state=None):
    """The recurrence one token a step, float32: the reference
    :func:`ssd_chunked` is tested against, not a path to train on. Same
    arguments and results (``y`` float32)."""
    f32 = jnp.float32
    heads, groups = x.shape[2], b_in.shape[2]
    x, dt, a, d = (t.astype(f32) for t in (x, dt, a, d))
    b_in = jnp.repeat(b_in.astype(f32), heads // groups, axis=2)
    c_in = jnp.repeat(c_in.astype(f32), heads // groups, axis=2)

    def step(state, now):
        x_t, dt_t, b_t, c_t = now
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        y_t = jnp.sum(state * c_t[..., None, :], axis=-1)
        return state, y_t + d[:, None] * x_t

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b_in.shape[-1:], f32) \
        if initial_state is None else initial_state.astype(f32)
    final, y = lax.scan(step, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b_in, c_in)))
    return jnp.moveaxis(y, 0, 1), final
