"""The mix of a CCA attention mixer (``models/decoder/mixers/cca.py``): what
lies between the latent projection ``u = [q0 | k0]`` and attention, as one
pass over the latent a direction. :func:`cca_mix` is two Pallas kernels
under one ``jax.custom_vjp`` (``hvd_cca_fwd``, ``hvd_cca_bwd``);
:func:`cca_mix_reference`, the plain ``jax.numpy`` lines it replaced, is what
the tests and ``scripts/conv_kernel_time.py --cca`` hold it to.

With ``Hq`` query and ``Hk`` key heads of ``D`` (``G = Hq / Hk``), taps
``(K0, K1)`` and ``u`` ``[B, S, (Hq + Hk) D]``::

    c1 = round(b0 + sum_k w0[k] * u_{t - (K0 - 1) + k})      depthwise
    c2[g] = b1[g] + sum_k c1_{t - (K1 - 1) + k}[g] @ W1[k, g]  a head a group
    qm[h] = (u[h] + u[Hq + h // G]) / 2;  km[j] = mean of qm over group j
    x = c2 + [qm | km]
    y[g] = rotary(sqrt(D) exp(temp[g]) x[g] / sqrt(|x[g]|^2 + 1e-6))

zeros before a sequence's start in both stages (``c1`` too: not its bias),
``temp`` zero on a query head, the rotary embedding rotate-half on the first
``rotary_dim`` dimensions of a head; everything float32 from ``u``'s dtype
but the two roundings to it the lines above show (``c1`` and the result) and
the grouped stage's operands (``c1`` and ``W1`` in ``u``'s dtype, the sum
float32).

A grid cell is a block of a sequence's tokens, all channels on the lanes, a
head a lane tile (a smaller head rides the next multiple of 128 lanes with
zeros, padded in one place, :func:`_to_lanes`), with the 16 tokens before it
(a second block of the same operand; after it too, backward) staged side by
side in a VMEM scratch; the kernels walk it in pieces of ``rows`` tokens, a
head at a time. The backward kernel makes the forward's intermediates again
for the piece and the few tokens after it whose cotangents its ``du`` reads,
writes ``du`` once and sums the parameters' cotangents in float32 in output
blocks that stay in VMEM along a sequence. The rule's residuals are its
inputs. Off the TPU the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .attention import rope
from .conv import causal_conv1d
from .pallas_util import LANES, NT, SUBLANES, TN, always, largest_divisor, \
    out_vma as _out_vma, use_interpret as _use_interpret, varying_like

# The kernels' names in the compiled program and in a device trace; their
# ``op_name`` ends ``layer<i>/attn/cca_mix/hvd_cca_*``, and the benchmark's
# readers find them by that scope (tests/test_program_names.py).
CCA_KERNEL_FWD = "hvd_cca_fwd"
CCA_KERNEL_BWD = "hvd_cca_bwd"
EPS = 1e-6  # under the root of a head's L2 norm
_HALO = SUBLANES  # tokens beside a block or a piece: a bfloat16 tile's rows
_FOLD = 8  # a float32 tile's rows: what a piece's sums are folded down to
# Tokens a grid cell and a piece of it hold at most. Swept on the chip at the
# zaya1-8b_s4096 cell's shape (scripts/conv_kernel_time.py --cca; PERF.md,
# Findings, PR 47): pieces of 64 and 128 tokens cost the backward kernel 75%
# and 14% more (the halo's share, a loop turn and the MXU's weights a piece);
# 512 to 2048 tokens a cell and pieces of 512 read within 4% of these.
_TOKENS, _ROWS = 512, 256
_VMEM_LIMIT = 64 << 20


def cca_mix_reference(u, conv0_w, conv0_b, conv1_w, conv1_b, temp, positions,
                      *, heads: int, kv_heads: int, rope_theta: float = 1e4,
                      rotary_dim=None):
    """``(q [B, S, Hq, D], k [B, S, Hk, D])`` in ``u``'s dtype from the
    latent ``u`` ``[B, S, (Hq + Hk) D]``: the module's equations as plain
    ``jax.numpy`` lines. ``conv0_w`` ``[K0, C]``, ``conv0_b`` ``[C]``,
    ``conv1_w`` ``[K1, Hq + Hk, D, D]`` (tap, head, channel in, channel out),
    ``conv1_b`` ``[C]``, ``temp`` ``[Hk]``; ``positions`` ``[B, S]`` or None
    for no rotary embedding. The form the kernels are held to; no program
    path calls it."""
    f32 = jnp.float32
    batch, seq, wide = u.shape
    groups = heads + kv_heads
    dim, group, q_dim = wide // groups, heads // kv_heads, \
        heads * (wide // groups)
    taps1 = conv1_w.shape[0]
    c1 = causal_conv1d(u, conv0_w, conv0_b).astype(u.dtype)
    # The grouped stage: a product a tap, the operands in the compute
    # dtype's values (the taps rounded to it, as every matrix of the model)
    # and the sum in float32. Handed over as float32: the MXU's one pass
    # takes them as what they are, and XLA's CPU backend cannot run a
    # batched bfloat16 product into a float32 result.
    c1 = jnp.pad(c1.reshape(batch, seq, groups, dim).astype(f32),
                 ((0, 0), (taps1 - 1, 0), (0, 0), (0, 0)))
    w1 = conv1_w.astype(u.dtype).astype(f32)
    c2 = sum(jnp.einsum("bsgi,gio->bsgo", c1[:, tap:tap + seq], w1[tap])
             for tap in range(taps1))
    c2 = c2.reshape(batch, seq, -1) + conv1_b
    q0 = u[..., :q_dim].astype(f32).reshape(batch, seq, kv_heads, group, dim)
    k0 = u[..., q_dim:].astype(f32).reshape(batch, seq, kv_heads, 1, dim)
    qm = 0.5 * (q0 + k0)
    q = c2[..., :q_dim] + qm.reshape(batch, seq, q_dim)
    k = c2[..., q_dim:].reshape(batch, seq, kv_heads, dim) \
        + jnp.mean(qm, axis=3)
    q = q.reshape(batch, seq, heads, dim)

    def unit(t):
        return t * (float(np.sqrt(dim)) * lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + EPS))

    q, k = unit(q), unit(k) * jnp.exp(temp.astype(f32))[:, None]
    if positions is not None:
        q = rope(q, positions, rope_theta, rotary_dim)
        k = rope(k, positions, rope_theta, rotary_dim)
    return q.astype(u.dtype), k.astype(u.dtype)


class _Plan(NamedTuple):
    """How a call of the mix's kernels is cut, from its shapes alone:
    ``heads`` query and ``kv_heads`` key heads of ``dim`` riding ``lanes``
    lanes each; the taps; ``rotary`` dimensions of a head turned (0: none);
    ``seq`` tokens carried (zeros beyond the sequence's own), ``tokens`` of
    them a grid cell, ``rows`` a piece of its walk."""
    heads: int
    kv_heads: int
    dim: int
    lanes: int
    taps0: int
    taps1: int
    rotary: int
    seq: int
    tokens: int
    rows: int

    @property
    def wide(self) -> int:
        return (self.heads + self.kv_heads) * self.lanes


def _plan(seq: int, heads: int, kv_heads: int, dim: int, taps0: int,
          taps1: int, rotary: int) -> _Plan:
    """The cut of a call, from its shapes alone."""
    if taps0 + taps1 - 2 > _HALO:
        raise ValueError(
            f"taps {(taps0, taps1)}: the two stages read {taps0 + taps1 - 2} "
            f"tokens before a token, and a block carries {_HALO}")
    rows = min(_ROWS, -(-seq // _HALO) * _HALO)
    pieces = -(-seq // rows)
    return _Plan(heads, kv_heads, dim, -(-dim // LANES) * LANES, taps0, taps1,
                 rotary, pieces * rows,
                 rows * largest_divisor(pieces, max(1, _TOKENS // rows)), rows)


def _rotary_table(plan: _Plan, positions, theta: float):
    """``[B, S, 2 lanes]`` float32: a head's lanes of ``[cos | cos | 1]`` and
    then of ``[-sin | sin | 0]`` (halves of ``rotary / 2``), so that the
    rotate-half embedding of a head ``n`` is ``n * first + partner(n) *
    second``, ``partner`` the other half's lane; :func:`rope`'s angles."""
    half, rest = plan.rotary // 2, plan.lanes - plan.rotary
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    beyond = angles.shape[:-1] + (rest,)
    return jnp.concatenate([
        cos, cos, jnp.ones(beyond, jnp.float32),
        -sin, sin, jnp.zeros(beyond, jnp.float32)], axis=-1)


class _Tile:
    """What both kernels do to a piece of a block: the reads of the staged
    operands, the depthwise stage, the grouped stage's products, the rotary
    embedding. ``few_ref`` holds the taps of the depthwise stage a row each,
    then its bias, the grouped stage's bias and the temperatures laid over
    their heads' lanes."""

    def __init__(self, plan: _Plan, few_ref, w1_ref, first_piece):
        self.plan, self.few, self.w1 = plan, few_ref, w1_ref
        self.first_piece = first_piece

    def lanes(self, g: int):
        return slice(g * self.plan.lanes, (g + 1) * self.plan.lanes)

    def row(self, r: int, g: int):
        return self.few[r:r + 1, self.lanes(g)]

    def scale(self, g: int):
        """``sqrt(D) exp(temp)`` over a head's lanes, ``[1, lanes]``."""
        return float(np.sqrt(self.plan.dim)) * jnp.exp(
            self.row(self.plan.taps0 + 2, g))

    def depthwise(self, ext, g: int):
        """``c1`` of the rows of ``ext`` (float32, ``_HALO`` rows of tokens
        before the piece first), rounded to ``dtype``'s values; zeros before
        a sequence's start."""
        taps = self.plan.taps0
        c1 = self.row(taps, g)
        for k in range(taps):
            shift = taps - 1 - k
            c1 = c1 + self.row(k, g) * (
                pltpu.roll(ext, shift, 0) if shift else ext)
        return jnp.concatenate([
            jnp.where(self.first_piece, 0, c1[:_HALO]), c1[_HALO:]], axis=0)

    def grouped(self, c1, g: int):
        """``c2`` of the rows of ``c1`` (in the operands' dtype) but its
        first ``_HALO``: a product a tap on all rows, moved by the tap's
        distance after it."""
        taps, n = self.plan.taps1, c1.shape[0]
        c2 = self.row(self.plan.taps0 + 1, g)
        for k in range(taps):
            shift = taps - 1 - k
            y = jnp.dot(c1, self.w1[k, g], preferred_element_type=jnp.float32)
            c2 = c2 + (pltpu.roll(y, shift, 0) if shift else y)[_HALO:n]
        return c2

    def partner(self, t):
        """Each rotary lane's other half's lane of ``t``; any value on the
        lanes the embedding leaves alone (their sine is zero)."""
        lanes, half = self.plan.lanes, self.plan.rotary // 2
        if 2 * half == lanes:
            return pltpu.roll(t, half, 1)
        lane = lax.broadcasted_iota(jnp.int32, t.shape, 1)
        return jnp.where(lane < half, pltpu.roll(t, lanes - half, 1),
                         pltpu.roll(t, half, 1))


def _stage(scr, ref, before=None, after=None):
    """A block and the ``_HALO`` tokens before and after it (each a ref's
    value or None) side by side in the scratch ``scr``."""
    at = 0
    for part in (before, ref[0], after):
        if part is not None:
            scr[at:at + part.shape[0]] = part
            at += part.shape[0]


def _fwd_kernel(*refs, plan: _Plan):
    """``q`` and ``k`` of a block of tokens, a piece of ``rows`` and a head
    at a time."""
    u_ref, before_ref, few_ref, w1_ref = refs[:4]
    rot_ref = refs[4] if plan.rotary else None
    q_ref, k_ref, u_scr = refs[-3:]
    f32 = jnp.float32
    rows, lanes, group = plan.rows, plan.lanes, plan.heads // plan.kv_heads
    first = pl.program_id(1) == 0

    def piece(p, _):
        start = pl.multiple_of(p * rows, rows)
        tile = _Tile(plan, few_ref, w1_ref, jnp.logical_and(first, p == 0))
        if plan.rotary:
            turn = rot_ref[0, pl.ds(start, rows)]

        def ext(g):
            return u_scr[pl.ds(start, _HALO + rows), tile.lanes(g)].astype(f32)

        def emit(out_ref, at: int, g: int, u_g, mean):
            c1 = tile.depthwise(u_g, g).astype(u_ref.dtype)
            x = tile.grouped(c1, g) + mean
            n = x * (lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + EPS)
                     * tile.scale(g))
            if plan.rotary:
                n = n * turn[:, :lanes] + tile.partner(n) * turn[:, lanes:]
            out_ref[0, pl.ds(start, rows), tile.lanes(at)] = n.astype(
                out_ref.dtype)

        for j in range(plan.kv_heads):
            u_k = ext(plan.heads + j)
            own_k, summed = u_k[_HALO:], None
            for h in range(j * group, (j + 1) * group):
                u_h = ext(h)
                emit(q_ref, h, h, u_h, 0.5 * (u_h[_HALO:] + own_k))
                summed = u_h[_HALO:] if summed is None \
                    else summed + u_h[_HALO:]
            emit(k_ref, j, plan.heads + j, u_k,
                 0.5 * (summed * (1.0 / group) + own_k))
        return 0

    @functools.partial(always, axis=1)
    def _():
        _stage(u_scr, u_ref, before=jnp.where(first, 0, before_ref[0]))
        lax.fori_loop(0, plan.tokens // rows, piece, 0)


def _bwd_kernel(*refs, plan: _Plan):
    """The cotangents of a block of tokens. A piece makes ``c1`` again on
    its rows and the ``_HALO`` either side, ``x`` on its rows and the
    ``_HALO`` after, and from ``dq``, ``dk`` there ``dx`` (the rotary
    embedding turned back, the norm's cotangent); ``dc1`` reads ``dx`` up to
    ``K1 - 1`` tokens later and ``du`` reads ``dc1`` up to ``K0 - 1`` later.
    The parameters' cotangents are summed over the piece's own rows: the
    grouped taps' as products on the MXU into ``dw1_ref``, the others folded
    to ``_FOLD`` rows into ``sums_ref`` (a kind a row of it: the depthwise
    taps, its bias, the grouped bias, the temperatures as ``dy y`` over
    their heads' lanes), both kept in VMEM from a sequence's first block to
    its last."""
    n_in = 11 if plan.rotary else 9
    (u_ref, before_ref, after_ref, dq_ref, dq_after_ref, dk_ref,
     dk_after_ref, few_ref, w1_ref) = refs[:9]
    rot_ref, rot_after_ref = refs[9:n_in] if plan.rotary else (None, None)
    du_ref, dw1_ref, sums_ref = refs[n_in:n_in + 3]
    u_scr, dq_scr, dk_scr = refs[n_in + 3:n_in + 6]
    rot_scr = refs[n_in + 6] if plan.rotary else None
    f32 = jnp.float32
    rows, lanes, group = plan.rows, plan.lanes, plan.heads // plan.kv_heads
    taps0, taps1 = plan.taps0, plan.taps1
    near = rows + _HALO  # the piece's rows and those after it
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1

    def later(t, shift: int):
        """Row ``i`` holds row ``i + shift`` of ``t``."""
        return pltpu.roll(t, t.shape[0] - shift, 0) if shift else t

    def fold(t):
        return sum(t[i:i + _FOLD] for i in range(0, rows, _FOLD))

    def piece(p, _):
        start = pl.multiple_of(p * rows, rows)
        tile = _Tile(plan, few_ref, w1_ref, jnp.logical_and(first, p == 0))
        if plan.rotary:
            turn = rot_scr[pl.ds(start, near)]

        def ext(g):
            return u_scr[pl.ds(start, near + _HALO), tile.lanes(g)] \
                .astype(f32)

        def back(dy_scr, at: int, g: int, u_g, mean, is_key: bool):
            """``(dx, the convolutions' part of du)`` of head ``g`` on the
            piece's own rows."""
            def add(kind: int, t):
                sums_ref[0, kind, :, tile.lanes(g)] += fold(t)

            c1 = tile.depthwise(u_g, g).astype(u_ref.dtype)
            x = tile.grouped(c1, g) + mean
            dn = dy_scr[pl.ds(start, near), tile.lanes(at)].astype(f32)
            if plan.rotary:
                dn = dn * turn[:, :lanes] - tile.partner(dn) * turn[:, lanes:]
            inv = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + EPS)
            along = jnp.sum(x * dn, axis=1, keepdims=True) * (inv * inv)
            gain = inv * tile.scale(g)
            if is_key:
                add(taps0 + 2, (dn * x * gain)[:rows])
            dx = (dn - along * x) * gain
            add(taps0 + 1, dx[:rows])
            dxb = dx.astype(u_ref.dtype)
            dc1 = None
            for k in range(taps1):
                shift = taps1 - 1 - k
                dw1_ref[0, k, g] += lax.dot_general(
                    c1[_HALO:near], later(dx, shift)[:rows].astype(
                        u_ref.dtype) if shift else dxb[:rows], TN,
                    preferred_element_type=f32)
                z = later(lax.dot_general(dxb, w1_ref[k, g], NT,
                                          preferred_element_type=f32), shift)
                dc1 = z if dc1 is None else dc1 + z
            du = None
            for k in range(taps0):
                shift = taps0 - 1 - k
                term = tile.row(k, g) * later(dc1, shift)[:rows]
                du = term if du is None else du + term
                add(k, dc1[:rows] * (pltpu.roll(u_g, shift, 0) if shift
                                     else u_g)[_HALO:near])
            add(taps0, dc1[:rows])
            return dx[:rows], du

        def write(g: int, t):
            du_ref[0, pl.ds(start, rows), tile.lanes(g)] = t.astype(
                du_ref.dtype)

        for j in range(plan.kv_heads):
            key = plan.heads + j
            u_k = ext(key)
            summed = None
            for h in range(j * group, (j + 1) * group):
                summed = ext(h)[_HALO:] if summed is None \
                    else summed + ext(h)[_HALO:]
            dx_k, du_k = back(dk_scr, j, key, u_k,
                              0.5 * (summed * (1.0 / group) + u_k[_HALO:]),
                              True)
            # x[h] reads (u[h] + u[key]) / 2 and x[key] the mean of those.
            means = None
            for h in range(j * group, (j + 1) * group):
                u_h = ext(h)
                dx_h, du_h = back(dq_scr, h, h, u_h,
                                  0.5 * (u_h[_HALO:] + u_k[_HALO:]), False)
                dm = dx_h + dx_k * (1.0 / group)
                write(h, du_h + 0.5 * dm)
                means = dm if means is None else means + dm
            write(key, du_k + 0.5 * means)
        return 0

    @functools.partial(always, axis=1)
    def _():
        @pl.when(first)
        def _():
            dw1_ref[...] = jnp.zeros_like(dw1_ref)
            sums_ref[...] = jnp.zeros_like(sums_ref)

        # Past a sequence's end the cotangents are zero, whatever the block
        # the clamped index fetched there holds.
        _stage(u_scr, u_ref, before=jnp.where(first, 0, before_ref[0]),
               after=jnp.where(last, 0, after_ref[0]))
        _stage(dq_scr, dq_ref, after=jnp.where(last, 0, dq_after_ref[0]))
        _stage(dk_scr, dk_ref, after=jnp.where(last, 0, dk_after_ref[0]))
        if plan.rotary:
            _stage(rot_scr, rot_ref, after=rot_after_ref[0])
        lax.fori_loop(0, plan.tokens // rows, piece, 0)


def _setup(kernel: str, plan: _Plan, u, weights):
    """What both calls share: the small operands as the kernels take them
    and the block specs by name; and, trace time only, the record of the
    cut behind ``hvd.metrics()``."""
    w0, b0, w1, b1, temp = weights
    wide, lanes = plan.wide, plan.lanes
    runtime.note_traced(
        "hvdtpu_spmd_cca_kernel_traces_total", kernel=kernel,
        tokens=plan.tokens, rows=plan.rows, heads=plan.heads,
        kv_heads=plan.kv_heads, head_lanes=plan.lanes,
        rotary_dim=plan.rotary, operand_dtype=jnp.dtype(u.dtype).name)
    over_keys = jnp.concatenate([
        jnp.zeros((plan.heads * lanes,), jnp.float32),
        jnp.repeat(temp, lanes)])
    few = jnp.concatenate([w0, b0[None], b1[None], over_keys[None]])
    few = jnp.pad(few, ((0, -few.shape[0] % _FOLD), (0, 0)))
    per, blocks = plan.tokens // _HALO, plan.seq // _HALO

    def before(t):
        return jnp.maximum(t * per - 1, 0)

    def after(t):
        return jnp.minimum((t + 1) * per, blocks - 1)

    def spec(tokens: int, width: int, at):
        return pl.BlockSpec((1, tokens, width), lambda b, t: (b, at(t), 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, t: (0,) * len(shape))

    q_wide, k_wide = plan.heads * lanes, plan.kv_heads * lanes
    specs = {
        "u": spec(plan.tokens, wide, lambda t: t),
        "u_before": spec(_HALO, wide, before),
        "u_after": spec(_HALO, wide, after),
        "q": spec(plan.tokens, q_wide, lambda t: t),
        "q_after": spec(_HALO, q_wide, after),
        "k": spec(plan.tokens, k_wide, lambda t: t),
        "k_after": spec(_HALO, k_wide, after),
        "rot": spec(plan.tokens, 2 * lanes, lambda t: t),
        "rot_after": spec(_HALO, 2 * lanes, after),
        "few": whole(few.shape), "w1": whole(w1.shape),
    }
    return few, w1.astype(u.dtype), specs


def _call(kernel: str, sequential: bool) -> dict:
    """``pallas_call``'s arguments both kernels share; ``sequential`` where
    a sequence's blocks add into output blocks that stay in VMEM."""
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",
                                 "arbitrary" if sequential else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(), name=kernel)


@functools.partial(jax.jit, inline=True, static_argnames=("plan",))
def _fwd_call(u, weights, rot, *, plan: _Plan):
    """``u`` ``[B, seq, wide]`` with a head a lane tile, the float32
    parameters at those lanes and the rotary table or None -> ``q`` ``[B,
    seq, heads lanes]`` and ``k`` ``[B, seq, kv_heads lanes]`` in ``u``'s
    dtype. (Jitted inline, as :func:`_bwd_call` is: the body is traced once
    for a shape, and a block's recomputed copy and the next layers re-bind
    it.)"""
    few, w1, specs = _setup(CCA_KERNEL_FWD, plan, u, weights)
    batch = u.shape[0]
    names = ("u", "u_before", "few", "w1") + (("rot",) if plan.rotary else ())
    args = (u, u, few, w1) + ((rot,) if plan.rotary else ())
    vma = _out_vma(*args)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(batch, plan.seq // plan.tokens),
        in_specs=[specs[name] for name in names],
        out_specs=[specs["q"], specs["k"]],
        out_shape=[
            jax.ShapeDtypeStruct((batch, plan.seq, width), u.dtype, vma=vma)
            for width in (plan.heads * plan.lanes,
                          plan.kv_heads * plan.lanes)],
        scratch_shapes=[pltpu.VMEM((_HALO + plan.tokens, plan.wide),
                                   u.dtype)],
        **_call(CCA_KERNEL_FWD, sequential=False))(*args)


@functools.partial(jax.jit, inline=True, static_argnames=("plan",))
def _bwd_call(u, weights, rot, dq, dk, *, plan: _Plan):
    """The cotangents of :func:`_fwd_call`'s ``u`` (in its dtype) and of
    its five parameters (float32, laid out as they came) for ``dq`` and
    ``dk`` in ``u``'s dtype."""
    few, w1, specs = _setup(CCA_KERNEL_BWD, plan, u, weights)
    batch, f32 = u.shape[0], jnp.float32
    taps0, lanes, groups = plan.taps0, plan.lanes, plan.heads + plan.kv_heads
    names = ("u", "u_before", "u_after", "q", "q_after", "k", "k_after",
             "few", "w1") + (("rot", "rot_after") if plan.rotary else ())
    args = (u, u, u, dq, dq, dk, dk, few, w1) \
        + ((rot, rot) if plan.rotary else ())
    vma = _out_vma(*args)
    dw1_shape = (batch,) + w1.shape
    sums_shape = (batch, taps0 + 3, _FOLD, plan.wide)
    near = plan.tokens + _HALO
    du, dw1, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(batch, plan.seq // plan.tokens),
        in_specs=[specs[name] for name in names],
        out_specs=[
            specs["u"],
            pl.BlockSpec((1,) + w1.shape, lambda b, t: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1,) + sums_shape[1:], lambda b, t: (b, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype, vma=vma),
                   jax.ShapeDtypeStruct(dw1_shape, f32, vma=vma),
                   jax.ShapeDtypeStruct(sums_shape, f32, vma=vma)],
        scratch_shapes=[
            pltpu.VMEM((near + _HALO, plan.wide), u.dtype),
            pltpu.VMEM((near, plan.heads * lanes), u.dtype),
            pltpu.VMEM((near, plan.kv_heads * lanes), u.dtype)]
        + ([pltpu.VMEM((near, 2 * lanes), f32)] if plan.rotary else []),
        **_call(CCA_KERNEL_BWD, sequential=True))(*args)
    sums = jnp.sum(sums, axis=(0, 2))
    dtemp = jnp.sum(sums[taps0 + 2].reshape(groups, lanes)[plan.heads:],
                    axis=1)
    return du, (sums[:taps0], sums[taps0], jnp.sum(dw1, axis=0),
                sums[taps0 + 1], dtemp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _mix(u, weights, positions, theta, plan):
    rot = _rotary_table(plan, positions, theta) if plan.rotary else None
    return tuple(_fwd_call(u, weights, rot, plan=plan))


def _mix_fwd(u, weights, positions, theta, plan):
    # The residuals are the inputs alone: no float32 intermediate is kept.
    return _mix(u, weights, positions, theta, plan), (u, weights, positions)


def _mix_bwd(theta, plan, kept, cotangents):
    u, weights, positions = kept
    dq, dk = (c.astype(u.dtype) for c in cotangents)
    rot = _rotary_table(plan, positions, theta) if plan.rotary else None
    du, dweights = _bwd_call(u, weights, rot, dq, dk, plan=plan)
    return du, dweights, None


_mix.defvjp(_mix_fwd, _mix_bwd)


def _to_lanes(t, axes, plan: _Plan):
    """``t`` with each of ``axes`` (of a head's ``dim``) carried to the
    head's ``lanes`` with zeros: the one place a head that is no multiple of
    the lane width is padded. Zeros stay zeros through both stages (their
    taps and biases are zeros too), add nothing to a head's norm and are cut
    off again by :func:`cca_mix`."""
    if plan.lanes == plan.dim:
        return t
    pad = [(0, 0)] * t.ndim
    for axis in axes:
        pad[axis] = (0, plan.lanes - plan.dim)
    return jnp.pad(t, pad)


def cca_mix(u, conv0_w, conv0_b, conv1_w, conv1_b, temp, positions, *,
            heads: int, kv_heads: int, rope_theta: float = 1e4,
            rotary_dim=None):
    """:func:`cca_mix_reference` in one pass over ``u`` a direction: two
    Pallas kernels under one ``jax.custom_vjp`` (``hvd_cca_fwd``,
    ``hvd_cca_bwd``), the same arguments and results; the cut comes from
    the shapes (:func:`_plan`)."""
    f32 = jnp.float32
    batch, seq, wide = u.shape
    groups = heads + kv_heads
    dim = wide // groups
    if groups * dim != wide or heads % kv_heads:
        raise ValueError(
            f"{heads} query and {kv_heads} key heads in {wide} channels")
    taps0, taps1 = conv0_w.shape[0], conv1_w.shape[0]
    rotary = 0 if positions is None else (rotary_dim or dim)
    if rotary % 2 or rotary > dim:
        raise ValueError(f"rotary_dim must be even and at most the head's "
                         f"{dim}, got {rotary}")
    plan = _plan(seq, heads, kv_heads, dim, taps0, taps1, rotary)

    def by_head(t):
        """``[..., C]`` with a head's channels carried to its lanes."""
        t = t.reshape(t.shape[:-1] + (groups, dim))
        return _to_lanes(t, (-1,), plan).reshape(t.shape[:-2] + (plan.wide,))

    weights = tuple(varying_like(w.astype(f32), u) for w in (
        by_head(conv0_w), by_head(conv0_b), _to_lanes(conv1_w, (2, 3), plan),
        by_head(conv1_b), temp))
    u = by_head(u)
    if plan.seq != seq:
        u = jnp.pad(u, ((0, 0), (0, plan.seq - seq), (0, 0)))
        if rotary:
            positions = jnp.pad(positions, ((0, 0), (0, plan.seq - seq)))
    q, k = _mix(u, weights, positions if rotary else None, float(rope_theta),
                plan)
    return (q.reshape(batch, plan.seq, heads, plan.lanes)[:, :seq, :, :dim],
            k.reshape(batch, plan.seq, kv_heads, plan.lanes)[:, :seq, :,
                                                            :dim])
