"""The causal depthwise convolution in front of both recurrent scans
(``ops/ssd.py``'s and ``ops/gated_delta.py``'s: ``models/decoder/mixers``'
two recurrent ones), with its SiLU: :func:`causal_conv_silu` takes the taps,
the bias, the SiLU and the cast in one pass over the tensor a direction, two
Pallas kernels under one ``jax.custom_vjp`` (``hvd_conv_fwd``,
``hvd_conv_bwd``). A grid cell holds about a megabyte of
the tensor and 16 (or 128) tokens of the cell before it, a second block of
the same operand; each tap is a rotation of the float32 tile along its token
axis, whichever of its two axes that is. The backward kernel makes the
pre-activation again from the input, for its tile and the few tokens after
it whose ``d pre`` the tile's ``du`` reads, and sums ``dw`` and ``db`` a cell
in float32. :func:`causal_conv1d`, the loop over taps, is the line the tests
hold it to. Off the TPU the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .pallas_util import LANES, SUBLANES, always, largest_divisor, \
    out_vma as _out_vma, use_interpret as _use_interpret, varying_like

# The kernels' names in the compiled program and in a device trace. The
# benchmark's readers count ``^hvd_ssd_`` and ``^hvd_gdn_`` into a scan's
# share: these match neither, and sit under the mixers' ``conv`` scope
# (tests/test_program_names.py).
CONV_KERNEL_FWD = "hvd_conv_fwd"
CONV_KERNEL_BWD = "hvd_conv_bwd"
_CONV_BLOCK = 1 << 19  # elements of the tensor a grid cell holds, about


def causal_conv1d(u, weight, bias):
    """Causal depthwise convolution along the sequence: ``u`` ``[B, S, C]``,
    ``weight`` ``[K, C]``, ``bias`` ``[C]`` or None -> float32 ``[B, S, C]``
    with ``out_t = bias + sum_k weight[k] u_{t - (K - 1) + k}`` and zeros
    before the start (tap ``K - 1`` reads the token itself). The plain form
    the tests and ``scripts/conv_kernel_time.py`` hold
    :func:`causal_conv_silu` to; no program path has called it since PR
    38."""
    taps, seq = weight.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        tap = padded[:, k:k + seq] * weight[k].astype(jnp.float32)
        out = tap if out is None else out + tap
    return out


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
_CONV_CUT = {False: (0, SUBLANES, 128, LANES, 4),
             True: (1, LANES, 2048, SUBLANES, 8)}


def _conv_plan(kernel, seq: int, channels: int, dtype, taps: int, bias: bool,
               tokens_minor: bool) -> _ConvPlan:
    """The cut of a call over ``seq`` tokens by ``channels``, from the shape
    alone; and, trace time only, the record of it behind
    ``hvd.metrics()``."""
    axis, halo, sub, rows, most = _CONV_CUT[tokens_minor]
    width = -(-channels // rows) * rows
    per_cell = rows * largest_divisor(width // rows, most)
    sub = min(sub, -(-seq // halo) * halo)
    pieces = -(-seq // sub)
    tokens = sub * largest_divisor(
        pieces, max(1, _CONV_BLOCK // (per_cell * sub)))
    plan = _ConvPlan(axis, halo, pieces * sub, width, tokens, per_cell, sub,
                     rows)
    runtime.note_traced(
        "hvdtpu_spmd_conv_kernel_traces_total", kernel=kernel,
        channels=channels, taps=taps, bias=str(bias).lower(),
        operand_dtype=jnp.dtype(dtype).name, tile=f"{tokens}x{per_cell}",
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

    always(lambda: _conv_walk(plan, piece, 0, lambda ch, carry: None))


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
    unit = 8 if axis == 0 else LANES  # a float32 tile along the tokens

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
    always(lambda: _conv_walk(plan, piece, (zero,) * (taps + 1), done))


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
    ``"tokens"`` (``[B, F, S]``: what ``ops/ssd.py::ssd_chunked``'s kernels read):
    the same arithmetic on a tile turned round, so that neither side copies.
    A length or a channel count the tile does not divide is carried with
    zeros and cut off again."""
    if minor not in ("channels", "tokens"):
        raise ValueError(f"minor={minor!r}: 'channels' or 'tokens'")
    if not 0 <= first <= u.shape[2] - weight.shape[1]:
        raise ValueError(
            f"channels {first} to {first + weight.shape[1]} of {u.shape[2]}")
    f32 = jnp.float32
    weight = varying_like(weight.astype(f32), u)
    if bias is not None:
        bias = varying_like(bias.astype(f32), u)
    return _conv_silu(u, weight, bias, first, minor == "tokens")
