"""Mamba-1's selective scan (Gu and Dao, arXiv:2312.00752, "S6"): a diagonal
state-space recurrence whose decay is one a **channel and state**, which
``ops/ssd.py``'s chunked matrix form (one scalar decay a head) does not
compute. For channel ``c`` (``u_t[c]``, ``dt_t[c] >= 0``) and state ``n``
(``B_t[n]``, ``C_t[n]``, shared by all channels)::

    s_t[n, c] = exp(dt_t[c] A[c, n]) s_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n C_t[n] s_t[n, c] + D[c] u_t[c]

The state over a sequence, ``[T, C, N]`` float32, is thousands of times the
inputs (5.4 GB a layer at 16,384 tokens of 5120 channels and 16 states) and
is never made in HBM. :func:`selective_scan` is two Pallas kernels under one
``jax.custom_vjp`` (``hvd_s6_fwd``, ``hvd_s6_bwd``): **channels on the lanes,
the states on the sublanes**, a block of channels' state ``[N, cb]`` float32
in VMEM scratch across the grid's last axis, which walks a sequence's blocks
of ``chunk`` tokens in order. A token is ``exp`` and a handful of
multiply-adds on the state's registers and one sum down the sublanes; ``u``,
``dt`` and ``y`` are read and written ``[tokens, channels]`` as the mixer has
them, 16 tokens at a time; ``B`` and ``C`` come transposed in groups of 16
tokens (``[T / 16, N, 16]``: a token's column is a static slice that
broadcasts along the lanes). The forward kernel also writes the state
entering each block (``[T / chunk, N, C]`` float32, ``1 / chunk`` of the
whole: ``SAVED_NAMES``); the backward kernel walks the blocks from the last
to the first, makes a block's states again from the one entering it into
VMEM scratch ``[chunk, N, cb]``, and runs the adjoint recurrence down them:
``ds_t = C_t dy_t + a_{t+1} ds_{t+1}``, from which every cotangent is a sum
over the state axis (down the sublanes: ``du``, ``d dt``), over the
channels (along the lanes: ``dB``, ``dC``, a block of channels' part, summed
outside) or over the tokens (``dA``, ``dD``, in the output block). Decays,
states and sums are float32; ``u``, ``B`` and ``C`` carry the compute
dtype's values. A length the chunk does not divide is padded with ``dt = 0``
rows, which neither decay the state nor add to it. :func:`s6_sequential`, one
token a step, is the line the tests hold it to. Off the TPU the kernels run
in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .pallas_util import LANES, SUBLANES, always, largest_divisor, \
    out_vma as _out_vma, use_interpret as _use_interpret, varying_like

# The kernels' names in the compiled program and in a device trace; the
# benchmark's readers match ``^hvd_s6_`` (tests/test_program_names.py): no
# reader of ``^hvd_ssd_`` or ``^hvd_conv_`` counts them into another scan.
KERNEL_FWD = "hvd_s6_fwd"
KERNEL_BWD = "hvd_s6_bwd"
# The state entering each block of ``chunk`` tokens, float32 ``[B, T / chunk,
# N, C]``: 4 N C / chunk bytes a token a layer (2560 at 5120 channels, 16
# states and 128 tokens a block, a quarter of the scan's output). The
# backward kernel starts each block from it; without it a checkpointed
# block's recomputed copy would run the forward kernel again for nothing else.
SAVED_NAMES = ("s6_scan_states",)
_GROUP = SUBLANES  # tokens read, and written, at a time: a bfloat16 tile
_MAX_TILES = 4     # lane tiles of channels a grid cell holds, at most
_CHUNK = 128       # tokens a grid cell: the backward's states in VMEM, 4 MiB


def s6_sequential(u, dt, a, b_in, c_in, d):
    """The recurrence one token a step, float32: what :func:`selective_scan`
    is tested against, not a path to train on. Same arguments; ``y``
    float32 ``[B, T, C]``."""
    f32 = jnp.float32
    u, dt, a, b_in, c_in, d = (t.astype(f32)
                               for t in (u, dt, a, b_in, c_in, d))

    def step(state, now):
        u_t, dt_t, b_t, c_t = now
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d * u_t

    start = jnp.zeros(u.shape[:1] + a.shape, f32)
    _, y = lax.scan(step, varying_like(start, u), tuple(
        jnp.moveaxis(t, 1, 0) for t in (u, dt, b_in, c_in)))
    return jnp.moveaxis(y, 0, 1)


def _token_rows(u_ref, dt_ref, at):
    """16 tokens' ``u`` and ``dt`` ``[16, cb]`` float32, and ``dt u``."""
    u = u_ref[0, at, :].astype(jnp.float32)
    dt = dt_ref[0, at, :]
    return u, dt, dt * u


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, enter_ref,
                state, rows, *, groups: int):
    """A grid cell: ``chunk`` tokens of one sequence and ``cb`` channels,
    whose float32 state ``[N, cb]`` stays in the scratch from the sequence's
    first block to its last (the grid's last axis, in order)."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    @always
    def _tokens():
        enter_ref[0, 0] = state[...]
        a, skip = a_ref[...], d_ref[...]

        def group(g, s):
            at = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            u, dt, dtu = _token_rows(u_ref, dt_ref, at)
            b, c = b_ref[0, g], c_ref[0, g]                   # [N, 16]
            for k in range(_GROUP):
                s = jnp.exp(dt[k:k + 1] * a) * s \
                    + dtu[k:k + 1] * b[:, k:k + 1]
                rows[k:k + 1, :] = jnp.sum(
                    s * c[:, k:k + 1], axis=0, keepdims=True) \
                    + skip * u[k:k + 1]
            y_ref[0, at, :] = rows[...].astype(y_ref.dtype)
            return s

        state[...] = lax.fori_loop(0, groups, group, state[...])


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, enter_ref, dy_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref, states,
                carry, du_rows, ddt_rows, *, groups: int):
    """The same cell, the grid's last axis walking a sequence's blocks from
    the last to the first. ``states[t]`` is the state entering token ``t``
    of the block, made again from the block's entering state; ``carry`` is
    ``a_{t+1} ds_{t+1}`` across blocks. ``dA`` ``[N, cb]`` and ``dD`` ``[1,
    cb]`` sum over a sequence's blocks in their output block."""
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        carry[...] = jnp.zeros(carry.shape, f32)
        da_ref[0] = jnp.zeros(da_ref.shape[1:], f32)
        dd_ref[0] = jnp.zeros(dd_ref.shape[1:], f32)

    @always
    def _tokens():
        a, skip = a_ref[...], d_ref[...]

        def forward(g, s):
            at = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            _, dt, dtu = _token_rows(u_ref, dt_ref, at)
            b = b_ref[0, g]
            for k in range(_GROUP):
                states[g * _GROUP + k] = s
                s = jnp.exp(dt[k:k + 1] * a) * s \
                    + dtu[k:k + 1] * b[:, k:k + 1]
            return s

        # Through the scratch: a loop's carry that starts as a scratch's
        # value keeps one type in interpret mode inside a ``shard_map``.
        states[0] = enter_ref[0, 0]
        lax.fori_loop(0, groups, forward, states[0])
        lane = lax.broadcasted_iota(jnp.int32, b_ref.shape[2:], 1)

        def backward(i, sums):
            ds_next, da, dd = sums
            g = groups - 1 - i
            at = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            u, dt, dtu = _token_rows(u_ref, dt_ref, at)
            dy = dy_ref[0, at, :].astype(f32)
            b, c = b_ref[0, g], c_ref[0, g]
            db, dc = jnp.zeros(b.shape, f32), jnp.zeros(b.shape, f32)
            for k in reversed(range(_GROUP)):
                entering = states[g * _GROUP + k]
                decay = jnp.exp(dt[k:k + 1] * a)
                b_k = b[:, k:k + 1]
                s = decay * entering + dtu[k:k + 1] * b_k
                ds = c[:, k:k + 1] * dy[k:k + 1] + ds_next
                # Along the lanes: a block of channels' part of dB and dC.
                dc = jnp.where(lane == k, jnp.sum(
                    s * dy[k:k + 1], axis=1, keepdims=True), dc)
                db = jnp.where(lane == k, jnp.sum(
                    ds * dtu[k:k + 1], axis=1, keepdims=True), db)
                ds_next = decay * ds
                through = ds_next * entering          # d exp(.) 's operand
                da = da + through * dt[k:k + 1]
                # Down the sublanes: the sums over the state axis.
                on_b = jnp.sum(ds * b_k, axis=0, keepdims=True)
                ddt_rows[k:k + 1, :] = jnp.sum(
                    through * a, axis=0, keepdims=True) + on_b * u[k:k + 1]
                du_rows[k:k + 1, :] = on_b * dt[k:k + 1] \
                    + skip * dy[k:k + 1]
                dd = dd + dy[k:k + 1] * u[k:k + 1]
            du_ref[0, at, :] = du_rows[...].astype(du_ref.dtype)
            ddt_ref[0, at, :] = ddt_rows[...]
            db_ref[0, 0, g] = db
            dc_ref[0, 0, g] = dc
            return ds_next, da, dd

        ds_next, da, dd = lax.fori_loop(
            0, groups, backward,
            (carry[...], jnp.zeros(a.shape, f32), jnp.zeros(skip.shape, f32)))
        carry[...] = ds_next
        da_ref[0] += da
        dd_ref[0] += dd


def _grouped(t, dtype=jnp.float32):
    """``[B, T, N]`` -> ``[B, T / 16, N, 16]``: a group of tokens' columns."""
    batch, tokens, state = t.shape
    return t.astype(dtype).reshape(batch, tokens // _GROUP, _GROUP, state) \
        .swapaxes(2, 3)


def _plan(kernel, body, u, a_t, chunk: int, backward: bool):
    """What both calls share: the block specs by name on the grid ``(batch,
    block of channels, block of tokens)`` and ``pallas_call``'s other
    arguments. ``u`` is ``[B, T, C]`` with ``T`` whole chunks and ``C`` whole
    lane tiles, ``a_t`` ``[N, C]``. The backward's index maps walk the token
    blocks from the last to the first; and, trace time only, the record of
    the call behind ``hvd.metrics()``."""
    batch, tokens, channels = u.shape
    state = a_t.shape[0]
    cb = LANES * largest_divisor(channels // LANES, _MAX_TILES)
    n_chunks = tokens // chunk
    if not _use_interpret() and state % 8:
        raise ValueError(
            f"{kernel} holds the {state} states on the sublanes: it needs a "
            "multiple of 8")
    runtime.note_traced(
        "hvdtpu_spmd_s6_kernel_traces_total", kernel=kernel, tokens=tokens,
        channels=channels, state=state, chunk=chunk,
        operand_dtype=jnp.dtype(u.dtype).name)

    def at(t):
        return n_chunks - 1 - t if backward else t

    per_chunk = chunk // _GROUP
    specs = {
        "tokens": pl.BlockSpec((1, chunk, cb), lambda b, c, t: (b, at(t), c)),
        "channels": pl.BlockSpec((state, cb), lambda b, c, t: (0, c)),
        "skip": pl.BlockSpec((1, cb), lambda b, c, t: (0, c)),
        "columns": pl.BlockSpec((1, per_chunk, state, _GROUP),
                                lambda b, c, t: (b, at(t), 0, 0)),
        "entering": pl.BlockSpec((1, 1, state, cb),
                                 lambda b, c, t: (b, at(t), 0, c)),
        # The backward's sums: a block of channels' part of dB and dC, and
        # dA and dD over a sequence's tokens.
        "column_parts": pl.BlockSpec((1, 1, per_chunk, state, _GROUP),
                                     lambda b, c, t: (b, c, at(t), 0, 0)),
        "channel_sums": pl.BlockSpec((1, state, cb),
                                     lambda b, c, t: (b, 0, c)),
        "skip_sums": pl.BlockSpec((1, 1, cb), lambda b, c, t: (b, 0, c)),
    }
    f32 = jnp.float32
    scratch = [pltpu.VMEM((state, cb), f32), pltpu.VMEM((_GROUP, cb), f32)]
    if backward:
        scratch = [pltpu.VMEM((chunk, state, cb), f32), scratch[0],
                   scratch[1], scratch[1]]
    call = dict(
        grid=(batch, channels // cb, n_chunks), scratch_shapes=scratch,
        # A sequence's blocks of tokens run in order: they share the state.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(), name=kernel)
    return specs, functools.partial(body, groups=per_chunk), call, cb


_FWD_SPECS = ("tokens", "tokens", "channels", "columns", "columns", "skip")


@functools.partial(jax.jit, inline=True, static_argnames="chunk")
def _fwd_call(u, dt, a_t, b_t, c_t, d, *, chunk: int):
    """``u`` ``[B, T, C]`` in the operand dtype, float32 ``dt`` ``[B, T,
    C]``, ``a_t`` ``[N, C]``, ``b_t``, ``c_t`` ``[B, T / 16, N, 16]`` and ``d``
    ``[1, C]`` -> ``y`` ``[B, T, C]`` in the operand dtype and the float32
    state entering each chunk ``[B, T / chunk, N, C]``."""
    specs, body, call, _ = _plan(KERNEL_FWD, _fwd_kernel, u, a_t, chunk,
                                 False)
    vma = _out_vma(u, dt, a_t, b_t, c_t, d)
    return pl.pallas_call(
        body, in_specs=[specs[name] for name in _FWD_SPECS],
        out_specs=[specs["tokens"], specs["entering"]],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                (u.shape[0], u.shape[1] // chunk) + a_t.shape, jnp.float32,
                vma=vma)],
        **call)(u, dt, a_t, b_t, c_t, d)


@functools.partial(jax.jit, inline=True, static_argnames="chunk")
def _bwd_call(u, dt, a_t, b_t, c_t, d, entering, dy, *, chunk: int):
    """The cotangents of :func:`_fwd_call`'s inputs for ``dy`` ``[B, T, C]``
    in the operand dtype: ``du`` in that dtype, the others float32."""
    specs, body, call, cb = _plan(KERNEL_BWD, _bwd_kernel, u, a_t, chunk,
                                  True)
    vma = _out_vma(u, dt, a_t, b_t, c_t, d, entering, dy)
    batch, _, channels = u.shape
    f32 = jnp.float32

    def like(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    parts = (batch, channels // cb) + b_t.shape[1:]
    du, ddt, da, db, dc, dd = pl.pallas_call(
        body,
        in_specs=[specs[name] for name in _FWD_SPECS + ("entering", "tokens")],
        out_specs=[specs[name] for name in (
            "tokens", "tokens", "channel_sums", "column_parts",
            "column_parts", "skip_sums")],
        out_shape=[like(u.shape, u.dtype), like(u.shape),
                   like((batch,) + a_t.shape), like(parts), like(parts),
                   like((batch, 1, channels))],
        **call)(u, dt, a_t, b_t, c_t, d, entering, dy)
    return (du, ddt, jnp.sum(da, axis=0), jnp.sum(db, axis=1),
            jnp.sum(dc, axis=1), jnp.sum(dd, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(chunk, u, dt, a_t, b_t, c_t, d):
    """The scan through the kernels: see :func:`_fwd_call`."""
    return _fwd_call(u, dt, a_t, b_t, c_t, d, chunk=chunk)[0]


def _scan_fwd(chunk, *inputs):
    y, entering = _fwd_call(*inputs, chunk=chunk)
    return y, inputs + (checkpoint_name(entering, "s6_scan_states"),)


def _scan_bwd(chunk, kept, dy):
    return _bwd_call(*kept, dy.astype(kept[0].dtype), chunk=chunk)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, dt, a, b_in, c_in, d, *, chunk: int = _CHUNK):
    """The recurrence above through the kernels.

    Args:
      u: ``[B, T, C]``, in the compute dtype.
      dt: ``[B, T, C]`` step sizes (after the soft-plus), >= 0; read float32.
      a: ``[C, N]`` float32, negative.
      b_in, c_in: ``[B, T, N]``, every channel's.
      d: ``[C]``, the skip.
      chunk: tokens a grid cell (a multiple of 16): what the backward
        kernel holds the states of in VMEM, and how far apart the kept
        states lie.

    Returns ``y`` ``[B, T, C]`` in ``u``'s dtype.
    """
    if chunk % _GROUP:
        raise ValueError(f"chunk={chunk}: a multiple of {_GROUP}")
    batch, tokens, channels = u.shape
    f32 = jnp.float32
    chunk = min(chunk, -(-tokens // _GROUP) * _GROUP)
    pad_t, pad_c = (-tokens) % chunk, (-channels) % LANES

    def padded(t, *, last: bool):
        # dt = 0: exp(0 A) = 1 keeps the state, dt u B = 0 adds nothing; a
        # padded channel's y is cut off again.
        return jnp.pad(t, ((0, 0), (0, pad_t), (0, pad_c if last else 0))) \
            if pad_t or (last and pad_c) else t

    a_t = jnp.pad(a.astype(f32).T, ((0, 0), (0, pad_c)))
    d = jnp.pad(d.astype(f32), (0, pad_c)).reshape(1, -1)
    y = _scan(chunk, padded(u, last=True),
              padded(dt.astype(f32), last=True), varying_like(a_t, u),
              _grouped(padded(b_in, last=False)),
              _grouped(padded(c_in, last=False)), varying_like(d, u))
    return y[:, :tokens, :channels]
