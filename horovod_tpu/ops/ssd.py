"""The state-space layer's mathematics (Mamba-2's "state-space duality"; Dao
and Gu, arXiv:2405.21060): the chunked scan, the causal depthwise
convolution in front of it, and the plain recurrence the tests hold the scan
to, as :func:`horovod_tpu.ops.attention.default_attention` is for the flash
kernels. The within-chunk term is two Pallas kernels (below), which also
add the entering state's part and the skip where ``y`` is written;
everything else is ``jax.numpy`` that XLA lowers.

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

**The convolution** in front of this scan and of the gated delta rule's
(``models/gpt.py``'s two recurrent mixers) is :func:`causal_conv_silu`: the
taps, the bias, the SiLU and the cast in one pass over the tensor a
direction, two more kernels under a ``custom_vjp`` of their own
(``hvd_conv_fwd``, ``hvd_conv_bwd``). A grid cell holds about a megabyte of
the tensor and 16 (or 128) tokens of the cell before it, a second block of
the same operand; each tap is a rotation of the float32 tile along its token
axis, whichever of its two axes that is. The backward kernel makes the
pre-activation again from the input, for its tile and the few tokens after
it whose ``d pre`` the tile's ``du`` reads, and sums ``dw`` and ``db`` a cell
in float32. :func:`causal_conv1d`, the loop over taps, is the line the tests
hold it to.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import div as _div, out_vma as _out_vma, rem as _rem, \
    use_interpret as _use_interpret

# The kernels' names in the compiled program and in a device trace; the
# benchmark's readers match ``^hvd_ssd_`` (tests/test_program_names.py).
KERNEL_FWD = "hvd_ssd_fwd"
KERNEL_BWD = "hvd_ssd_bwd"
_LANES = 128
_TILE = _LANES    # a chunk is cut into tiles of this side (the lane width)
_SUBLANES = 16    # rows of a bfloat16 tile: a head's rows start on one
_MAX_HEADS = 16   # heads a grid cell, at most
_NEG_INF = -1e30  # the masked exponent: exp gives 0, and no inf - inf
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ: contract the last dim of both
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b: contract the first dim of both
# The convolution's kernels. The benchmark's readers count ``^hvd_ssd_`` and
# ``^hvd_gdn_`` into a scan's share: these match neither, and sit under the
# mixers' ``conv`` scope.
CONV_KERNEL_FWD = "hvd_conv_fwd"
CONV_KERNEL_BWD = "hvd_conv_bwd"
_CONV_BLOCK = 1 << 19  # elements of the tensor a grid cell holds, about


def causal_conv1d(u, weight, bias):
    """Causal depthwise convolution along the sequence: ``u`` ``[B, S, C]``,
    ``weight`` ``[K, C]``, ``bias`` ``[C]`` or None -> float32 ``[B, S, C]``
    with ``out_t = bias + sum_k weight[k] u_{t - (K - 1) + k}`` and zeros
    before the start (tap ``K - 1`` reads the token itself)."""
    taps, seq = weight.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        tap = padded[:, k:k + seq] * weight[k].astype(jnp.float32)
        out = tap if out is None else out + tap
    return out


def _by_group(t, groups: int, axis: int):
    """The heads on ``axis`` split into ``[G, H / G]``."""
    return t.reshape(t.shape[:axis] + (groups, t.shape[axis] // groups)
                     + t.shape[axis + 1:])


def _varying_like(x, like):
    """``x`` marked as varying over the mesh axes ``like`` varies over:
    inside a ``shard_map`` a scan's carry must enter with the type it leaves
    with, and a ``custom_vjp``'s cotangent come back with its input's (the
    mark's own transpose sums a replicated parameter's over the ranks)."""
    axes = jax.typeof(like).vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(axes), to="varying") if axes else x


def _always(body):
    """Run ``body`` under a predicate that always holds, NOT unguarded:
    interpret mode inside a ``shard_map`` matches the varying axes of a
    block's fetch only along a ``pl.when`` path
    (``flash_attention.Mask.tile_kept``); compiled, Mosaic folds the constant."""
    pl.when(pl.program_id(2) >= 0)(body)


def _largest_divisor(n: int, most: int) -> int:
    return next(d for d in range(min(most, n), 0, -1) if n % d == 0)


def heads_per_block(heads_per_group: int) -> int:
    """Heads a grid cell of the kernels holds: the largest divisor of a
    group's heads, ``_MAX_HEADS`` at most."""
    return _largest_divisor(heads_per_group, _MAX_HEADS)


# ---- The convolution in front of both scans, one pass a direction ----------

class _ConvPlan(NamedTuple):
    """How a call of the convolution's kernels is cut. ``axis`` is the token
    axis of a tile: 0, ``[tokens, channels]``, channels on the lanes; 1,
    ``[channels, tokens]``, tokens on the lanes. The tensor is carried at
    ``seq`` tokens by ``width`` channels (zeros beyond its own); a grid cell
    holds ``tokens`` by ``channels`` of it and the ``halo`` tokens either
    side; the kernel's loop takes ``sub`` tokens by ``rows`` channels a
    pass."""
    axis: int
    halo: int
    seq: int
    width: int
    tokens: int
    channels: int
    sub: int
    rows: int


# (token axis, halo, tokens a pass at most, channels a pass, passes of
# channels a grid cell at most), by whether the tokens are minor. The halo is
# the least a block of the operand dtype can hold along the token axis: a
# bfloat16 tile's 16 sublanes, or the lanes. Swept on the chip at the three
# cells' shapes (scripts/conv_kernel_time.py; PERF.md, Findings, PR 38): more
# tokens a pass cost the backward kernel with channels on the lanes (256:
# +13%) and fewer with tokens on them (1024: +20%, the halo's share).
_CONV_CUT = {False: (0, _SUBLANES, 128, _LANES, 4),
             True: (1, _LANES, 2048, _SUBLANES, 8)}


def _conv_plan(kernel, seq: int, channels: int, dtype, taps: int, bias: bool,
               tokens_minor: bool) -> _ConvPlan:
    """The cut of a call over ``seq`` tokens by ``channels``, from the shape
    alone; and, trace time only, the record of it behind
    ``hvd.metrics()``."""
    axis, halo, sub, rows, most = _CONV_CUT[tokens_minor]
    width = -(-channels // rows) * rows
    per_cell = rows * _largest_divisor(width // rows, most)
    sub = min(sub, -(-seq // halo) * halo)
    pieces = -(-seq // sub)
    tokens = sub * _largest_divisor(
        pieces, max(1, _CONV_BLOCK // (per_cell * sub)))
    plan = _ConvPlan(axis, halo, pieces * sub, width, tokens, per_cell, sub,
                     rows)
    from .. import runtime
    recorder = runtime.recorder()
    if recorder is not None:
        recorder.note_traced(
            "hvdtpu_spmd_conv_kernel_traces_total", kernel=kernel,
            channels=channels, taps=taps, bias=str(bias).lower(),
            operand_dtype=jnp.dtype(dtype).name,
            tile=f"{tokens}x{per_cell}",
            minor="tokens" if tokens_minor else "channels")
    return plan


def _cut(t, lo: int, n: int, axis: int):
    return lax.slice_in_dim(t, lo, lo + n, axis=axis)


def _tile_at(plan: _ConvPlan, tokens, channels):
    """The index of ``tokens`` by ``channels`` in a cell's block ``[1, .,
    .]`` of the tensor."""
    return (0, tokens, channels) if plan.axis == 0 else (0, channels, tokens)


def _conv_row(plan: _ConvPlan, wb_ref, k: int, ch):
    """Row ``k`` of the taps-and-bias operand for the channels ``ch``, shaped
    to multiply a piece: ``[1, rows]`` or ``[rows, 1]``."""
    return wb_ref[k:k + 1, ch] if plan.axis == 0 else wb_ref[ch, k:k + 1]


def _conv_walk(plan: _ConvPlan, piece, carry, done):
    """``carry = piece(j, start, ch, carry)`` over the pieces of a cell's
    block, a channel piece's token pieces in order, then ``done(ch,
    carry)``. The axis on the sublanes is walked by a ``fori_loop`` (a
    start Mosaic sees as a multiple of a tile), the axis on the lanes in
    Python: what is traced and compiled is one row of pieces. (Both in
    loops compile as fast and run the same with channels on the lanes, and
    10-13% slower with tokens on them, where a piece's start would be a
    lane offset the kernel cannot see: my chip run, PR 38.)"""
    n_tok, n_ch = plan.tokens // plan.sub, plan.channels // plan.rows
    if plan.axis == 0:
        for c in range(n_ch):
            ch = slice(c * plan.rows, (c + 1) * plan.rows)

            def tokens(j, carry, ch=ch):
                return piece(j, pl.multiple_of(j * plan.sub, plan.sub), ch,
                             carry)

            done(ch, lax.fori_loop(0, n_tok, tokens, carry))
    else:
        def channels(r, _):
            ch = pl.ds(pl.multiple_of(r * plan.rows, plan.rows), plan.rows)
            acc = carry
            for j in range(n_tok):
                acc = piece(j, j * plan.sub, ch, acc)
            done(ch, acc)
            return 0

        lax.fori_loop(0, n_ch, channels, 0)


def _conv_beside(plan: _ConvPlan, ref, edge, j, start, ch, *, after: bool):
    """The ``halo`` tokens before (or after) piece ``j`` of a cell's block
    ``ref``: the block's own, or at its first (last) piece ``edge``, the
    neighbouring block's."""
    at_edge = plan.tokens // plan.sub - 1 if after else 0
    at = start + plan.sub if after else start - plan.halo
    if isinstance(j, int):
        return edge if j == at_edge \
            else ref[_tile_at(plan, pl.ds(at, plan.halo), ch)]
    at = pl.multiple_of(jnp.clip(at, 0, plan.tokens - plan.halo), plan.halo)
    return jnp.where(j == at_edge, edge,
                     ref[_tile_at(plan, pl.ds(at, plan.halo), ch)])


def _conv_taps(plan: _ConvPlan, ext, lo: int, n: int, wb_ref, ch, taps: int,
               bias: bool):
    """The float32 pre-activation of the ``n`` tokens from ``lo`` of ``ext``
    (tokens ``lo - (taps - 1)`` on must be in it), summed in
    :func:`causal_conv1d`'s order; and each tap's input, the tokens
    shifted."""
    out = _conv_row(plan, wb_ref, taps, ch) if bias else None
    moved = []
    for k in range(taps):
        shift = taps - 1 - k
        moved.append(_cut(
            pltpu.roll(ext, shift, plan.axis) if shift else ext, lo, n,
            plan.axis))
        tap = moved[-1] * _conv_row(plan, wb_ref, k, ch)
        out = tap if out is None else out + tap
    return out, moved


def _conv_fwd_kernel(u_ref, before_ref, wb_ref, y_ref, *, plan: _ConvPlan,
                     taps: int, bias: bool):
    """``y = silu(bias + sum_k w_k u_{t - (K - 1) + k})``, rounded once, a
    piece at a time: the piece and the halo before it side by side in
    float32, each tap a rotation of that along the tokens."""
    f32 = jnp.float32
    first = pl.program_id(1) == 0

    def piece(j, start, ch, carry):
        here = _tile_at(plan, pl.ds(start, plan.sub), ch)
        edge = jnp.where(first, 0, before_ref[_tile_at(plan, slice(None), ch)])
        ext = jnp.concatenate(
            [_conv_beside(plan, u_ref, edge, j, start, ch, after=False)
             .astype(f32), u_ref[here].astype(f32)], axis=plan.axis)
        pre, _ = _conv_taps(plan, ext, plan.halo, plan.sub, wb_ref, ch, taps,
                            bias)
        y_ref[here] = jax.nn.silu(pre).astype(y_ref.dtype)
        return carry

    _always(lambda: _conv_walk(plan, piece, 0, lambda ch, carry: None))


def _conv_bwd_kernel(u_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     wb_ref, du_ref, sums_ref, *, plan: _ConvPlan, taps: int,
                     bias: bool):
    """The forward's cotangents, a piece at a time. The pre-activation is
    made again for the piece and the halo after it (``du_t`` reads ``d pre``
    up to ``t + K - 1``), ``d pre = dy silu'(pre)`` in float32, ``du_t =
    sum_k w_k d pre_{t + (K - 1) - k}`` rounded once; the taps' and the
    bias's cotangents are summed over the cell's tokens in float32, a row a
    tap and the bias's last, and over the cells outside."""
    f32 = jnp.float32
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    n, halo, axis = plan.sub, plan.halo, plan.axis
    unit = 8 if axis == 0 else _LANES  # a float32 tile along the tokens

    def fold(t):
        """``t`` summed over its tokens down to one tile of them."""
        return sum(_cut(t, i, unit, axis) for i in range(0, n, unit))

    def piece(j, start, ch, sums):
        here = _tile_at(plan, pl.ds(start, n), ch)
        edge = _tile_at(plan, slice(None), ch)
        ext = jnp.concatenate([
            _conv_beside(plan, u_ref, jnp.where(first, 0, before_ref[edge]),
                         j, start, ch, after=False).astype(f32),
            u_ref[here].astype(f32),
            _conv_beside(plan, u_ref, after_ref[edge], j, start, ch,
                         after=True).astype(f32)], axis=axis)
        pre, moved = _conv_taps(plan, ext, halo, n + halo, wb_ref, ch, taps,
                                bias)
        # Past a sequence's end dy is zero, whatever the block there holds.
        dy = jnp.concatenate([
            dy_ref[here].astype(f32),
            _conv_beside(plan, dy_ref, jnp.where(last, 0, dy_after_ref[edge]),
                         j, start, ch, after=True).astype(f32)], axis=axis)
        gate = jax.nn.sigmoid(pre)
        dpre = dy * (gate * (1 + pre * (1 - gate)))
        du = None
        for k in range(taps):
            shift = taps - 1 - k
            term = _cut(pltpu.roll(dpre, n + halo - shift, axis) if shift
                        else dpre, 0, n, axis) * _conv_row(plan, wb_ref, k, ch)
            du = term if du is None else du + term
        du_ref[here] = du.astype(du_ref.dtype)
        own = _cut(dpre, 0, n, axis)
        return tuple(s + fold(own * _cut(m, 0, n, axis))
                     for s, m in zip(sums, moved)) + (sums[-1] + fold(own),)

    def done(ch, sums):
        for k, s in enumerate(sums):
            at = (0, 0, slice(k, k + 1), ch) if axis == 0 \
                else (0, 0, ch, slice(k, k + 1))
            sums_ref[at] = jnp.sum(s, axis=axis, keepdims=True)

    zero = jnp.zeros((unit, plan.rows) if axis == 0 else (plan.rows, unit),
                     f32)
    _always(lambda: _conv_walk(plan, piece, (zero,) * (taps + 1), done))


def _conv_setup(kernel, body, u, weight, bias, first: int,
                tokens_minor: bool):
    """What both calls share: the cut; the tensor as the kernels take it,
    tokens last if they are minor; the block of channels its first one lies
    in; a function that lays a further operand out like the convolution's
    channels and its inverse; the float32 taps with the bias as one more
    row; the block specs by name; ``pallas_call``'s other arguments. The
    kernels read channels ``first`` on straight out of ``u`` where the cut
    divides them and the length (the three cells'); else a slice of it,
    carried with zeros up to the cut's length and width."""
    taps, channels = weight.shape
    batch, seq = u.shape[:2]
    plan = _conv_plan(kernel, seq, channels, u.dtype, taps, bias is not None,
                      tokens_minor)
    halo, axis = plan.halo, plan.axis

    def lay(t):
        t = jnp.pad(t, ((0, 0), (0, plan.seq - seq),
                        (0, plan.width - channels))) \
            if (plan.seq, plan.width) != (seq, channels) else t
        return t.swapaxes(1, 2) if axis else t

    def unlay(t):
        return (t.swapaxes(1, 2) if axis else t)[:, :seq, :channels]

    if (plan.seq, plan.width) == (seq, channels) \
            and first % plan.channels == 0:
        u, first = (u.swapaxes(1, 2) if axis else u), first // plan.channels
    else:
        u, first = lay(lax.slice_in_dim(u, first, first + channels, axis=2)), 0
    wb = jnp.concatenate([
        weight, (jnp.zeros_like(weight[0]) if bias is None else bias)[None]])
    wb = jnp.pad(wb, ((0, 0), (0, plan.width - channels)))

    def spec(tokens: int, at, first: int = 0):
        """A block of ``tokens`` by the cell's channels, its index along the
        tokens ``at(t)``, along the channels ``first`` blocks on."""
        if axis:
            return pl.BlockSpec((1, plan.channels, tokens),
                                lambda b, t, c: (b, first + c, at(t)))
        return pl.BlockSpec((1, tokens, plan.channels),
                            lambda b, t, c: (b, at(t), first + c))

    per, blocks = plan.tokens // halo, plan.seq // halo
    few = (taps + 1, plan.channels)

    def before(t):
        return jnp.maximum(t * per - 1, 0)

    def after(t):
        return jnp.minimum((t + 1) * per, blocks - 1)

    specs = {
        "u": spec(plan.tokens, lambda t: t, first),
        "u_before": spec(halo, before, first),
        "u_after": spec(halo, after, first),
        "tile": spec(plan.tokens, lambda t: t),
        "after": spec(halo, after),
        "taps": pl.BlockSpec(few[::-1], lambda b, t, c: (c, 0)) if axis
        else pl.BlockSpec(few, lambda b, t, c: (0, c)),
        "sums": pl.BlockSpec((1, 1) + few[::-1], lambda b, t, c: (b, t, c, 0))
        if axis else pl.BlockSpec((1, 1) + few, lambda b, t, c: (b, t, 0, c)),
    }
    call = dict(
        grid=(batch, plan.seq // plan.tokens, plan.width // plan.channels),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=_use_interpret(), name=kernel)
    body = functools.partial(body, plan=plan, taps=taps,
                             bias=bias is not None)
    return plan, u, lay, unlay, wb.T if axis else wb, specs, body, call


@functools.partial(jax.jit, inline=True,
                   static_argnames=("first", "tokens_minor"))
def _conv_fwd_call(u, weight, bias, *, first: int, tokens_minor: bool):
    """``u`` ``[B, S, F]``, float32 ``weight`` ``[K, C]`` and ``bias`` ``[C]``
    or None -> ``silu`` of the convolution of ``u``'s channels ``first`` to
    ``first + C``, ``[B, S, C]`` in ``u``'s dtype. (Jitted inline, as
    :func:`_conv_bwd_call` is: the body is traced once for a shape, and a
    block's recomputed copy and the next layers re-bind it.)"""
    plan, u, _, unlay, wb, specs, body, call = _conv_setup(
        CONV_KERNEL_FWD, _conv_fwd_kernel, u, weight, bias, first,
        tokens_minor)
    shape = (u.shape[0],) + ((plan.width, plan.seq) if plan.axis
                             else (plan.seq, plan.width))
    return unlay(pl.pallas_call(
        body, in_specs=[specs[name] for name in ("u", "u_before", "taps")],
        out_specs=specs["tile"],
        out_shape=jax.ShapeDtypeStruct(shape, u.dtype, vma=_out_vma(u, wb)),
        **call)(u, u, wb))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("first", "tokens_minor"))
def _conv_bwd_call(u, weight, bias, dy, *, first: int, tokens_minor: bool):
    """The cotangents of :func:`_conv_fwd_call`'s inputs for ``dy`` ``[B, S,
    C]`` in ``u``'s dtype: ``du`` of the convolution's channels alone, in that
    dtype too, float32 ``dw`` ``[K, C]`` and ``db`` ``[C]``."""
    plan, u, lay, unlay, wb, specs, body, call = _conv_setup(
        CONV_KERNEL_BWD, _conv_bwd_kernel, u, weight, bias, first,
        tokens_minor)
    taps, channels = weight.shape
    dy = lay(dy)
    vma = _out_vma(u, dy, wb)
    cells = call["grid"][:2]
    few = (taps + 1, plan.width)
    du, sums = pl.pallas_call(
        body, in_specs=[specs[name] for name in (
            "u", "u_before", "u_after", "tile", "after", "taps")],
        out_specs=[specs["tile"], specs["sums"]],
        out_shape=[
            jax.ShapeDtypeStruct(dy.shape, u.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                cells + (few[::-1] if plan.axis else few), jnp.float32,
                vma=vma)],
        **call)(u, u, u, dy, dy, wb)
    sums = jnp.sum(sums, axis=(0, 1))
    sums = (sums.T if plan.axis else sums)[:, :channels]
    return unlay(du), sums[:taps], sums[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_silu(u, weight, bias, first, tokens_minor):
    return _conv_fwd_call(u, weight, bias, first=first,
                          tokens_minor=tokens_minor)


def _conv_silu_fwd(u, weight, bias, first, tokens_minor):
    # The residuals are the inputs alone: the pre-activation is never kept.
    return _conv_silu(u, weight, bias, first, tokens_minor), (u, weight, bias)


def _conv_silu_bwd(first, tokens_minor, kept, dy):
    u, weight, bias = kept
    du, dw, db = _conv_bwd_call(u, weight, bias, dy.astype(u.dtype),
                                first=first, tokens_minor=tokens_minor)
    beyond = u.shape[2] - first - weight.shape[1]
    if first or beyond:  # the channels the convolution did not read
        du = jnp.pad(du, ((0, 0), (0, 0), (first, beyond)))
    return du, dw, None if bias is None else db


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv_silu(u, weight, bias, *, first: int = 0,
                     minor: str = "channels"):
    """``silu(causal_conv1d(u[..., first:first + C], weight,
    bias)).astype(u.dtype)`` in one pass over the tensor a direction: two
    Pallas kernels under one ``jax.custom_vjp`` (``hvd_conv_fwd``,
    ``hvd_conv_bwd``) that read ``u`` ``[B, S, F]`` in its own dtype, take
    the taps ``[K, C]``, the bias and the SiLU in float32 in
    :func:`causal_conv1d`'s order and round once; the backward kernel makes
    the pre-activation again from ``u``, which with the taps is all the rule
    keeps, and sums the taps' and the bias's cotangents in float32. ``u``
    may be wider than the convolution (a projection's whole output: the
    kernels read their ``C`` channels from ``first`` on in place, no slice
    of it is made). ``minor`` says which axis of the tensor the caller's
    neighbours have on the lanes, ``"channels"`` (``[B, S, F]`` as it is) or
    ``"tokens"`` (``[B, F, S]``: what :func:`ssd_chunked`'s kernels read):
    the same arithmetic on a tile turned round, so that neither side copies.
    A length or a channel count the tile does not divide is carried with
    zeros and cut off again."""
    if minor not in ("channels", "tokens"):
        raise ValueError(f"minor={minor!r}: 'channels' or 'tokens'")
    if not 0 <= first <= u.shape[2] - weight.shape[1]:
        raise ValueError(
            f"channels {first} to {first + weight.shape[1]} of {u.shape[2]}")
    f32 = jnp.float32
    weight = _varying_like(weight.astype(f32), u)
    if bias is not None:
        bias = _varying_like(bias.astype(f32), u)
    return _conv_silu(u, weight, bias, first, minor == "tokens")


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
            tile != _TILE or width % _SUBLANES
            or (groups > 1 and state % _SUBLANES)):
        raise ValueError(
            f"{kernel} does not tile chunk={chunk}, head_dim={width}, "
            f"state={state} in {groups} groups: it needs a chunk that is a "
            f"multiple of {_TILE}, a head_dim that is a multiple of "
            f"{_SUBLANES} and, with several groups, such a state")
    from .. import runtime
    recorder = runtime.recorder()
    if recorder is not None:
        recorder.note_traced(
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
        exponent = jnp.where(keep, exponent, _NEG_INF)
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
        cbt_scr[:] = lax.dot_general(b_ref[0], c_ref[0], _TN,
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

    @_always
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

    @_always
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
                        dy[i], (decay * cbt).astype(dy[i].dtype), _NT,
                        preferred_element_type=jnp.float32)
                    dxdt = part if dxdt is None else dxdt + part
                    dcb = decay * lax.dot_general(
                        xdt, dy[i], _TN, preferred_element_type=jnp.float32)
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
    from .. import runtime
    recorder = runtime.recorder()
    if recorder is not None:
        recorder.note_traced(
            "hvdtpu_spmd_ssm_layer_traces_total", heads=heads,
            head_dim=width, state=state, groups=groups, chunk=chunk)

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
        carry_on, _varying_like(start, own),
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
                     _varying_like(d.astype(f32), x))
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
