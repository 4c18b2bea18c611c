"""Kimi delta attention (KDA): the gated delta rule with a decay **a key
channel**. The chunked form is four Pallas kernels (``hvd_kda_*``), laid out
as ``ops/gated_delta.py``'s ``hvd_gdn_*`` are; the plain form the tests hold
them to is ``jax.numpy``.

A head keeps a state ``S`` in ``R^{K x V}``, zero at the start of a sequence.
With ``alpha_t = exp(g_t)`` in ``(0, 1]^K`` (``g_t`` the log decay, a vector)
and ``beta_t`` the writing strength::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(Kimi Linear, arXiv:2510.26692, as remembered; there is no network here.)
With every channel's decay alike it is ``ops/gated_delta.py``'s rule. ``q``
and ``k`` enter L2-normalised a head, ``q`` over the root of the head's size
besides; under ``norm_qk`` the chunk-local kernels make the norm in VMEM as
the gated delta rule's do (``gated_delta.unit_rows`` is the plain line).

:func:`kda_sequential` is that recurrence one token a step, float32.
:func:`kda_chunked` computes the same in chunks of ``chunk`` tokens. With
``c_t = sum_{i <= t} g_i`` inside a chunk, ``Gamma_t = exp(c_t)`` a channel
and ``u_t`` what token ``t`` really writes::

    (I + A) U = beta V - (beta (K * Gamma)) S,
        A_tj = beta_t <k_t * Gamma_t, k_j / Gamma_j>  for j < t, else 0
    T = (I + A)^{-1}
    o_t = (q_t * Gamma_t)^T S + sum_{j <= t} <q_t * Gamma_t, k_j / Gamma_j> u_j
    S' = Diag(Gamma_last) S + sum_j (k_j * Gamma_last / Gamma_j) u_j^T

**Why sub-blocks.** A scalar decay comes out of ``K K^T`` as a ``[Q, Q]`` tile
``exp(c_t - c_j)``; a vector decay does not: the products are ``(K *
Gamma)(K / Gamma)^T`` and ``1 / Gamma`` overflows float32 inside a chunk (a
gate at its lower bound of -5 for 64 tokens: ``exp(320)``). So the gate is
bounded below (the caller's, ``lower_bound``), and ``k_j / Gamma_j`` is taken
against the first token of the **row's** ``sub_chunk``-token sub-block, never
the chunk's: for row sub-block ``i`` with ``r_i = c`` at its first token, the
rows are ``k_t exp(c_t - r_i)`` (exponent <= 0) and the columns ``k_j exp(r_i
- c_j)``, whose exponent is non-positive for every ``j`` before the sub-block
and at most ``|lower_bound| (sub_chunk - 1)`` inside it (75 at -5 and 16:
``exp(75)`` is 3.7e32). Columns after the sub-block are masked; their
exponent is clamped at :data:`MAX_EXPONENT` so that what is masked is finite.
A bound the sub-block does not hold raises by name.

**The chunk-local kernels** (``hvd_kda_fwd``, ``hvd_kda_bwd``): a grid cell
is a few chunks of one sequence (a loop) and one head. The running sums
``c`` (a product with a triangle of ones, float32 at the highest precision),
the decayed ``K K^T`` and ``Q K^T`` by sub-blocks, ``A``, ``T`` (float32:
``pallas_util.unit_lower_inverse_in_vmem``, shared with the gated delta
rule), ``u_own = T (beta V)`` (float32), ``w = T (beta K Gamma)``, ``attn``,
``q Gamma`` and ``k Gamma_last / Gamma`` come out in the recurrence's order
``[c, B, H, Q, .]``. **The inverse is made once a step** (PR 68): the
forward kernel writes the float32 ``T`` it holds, before its rounding, as a
sixth output, a chunk's lower 32 rows beside its upper 32 (``[c, B, H, 32,
128]`` at a chunk of 64, ``pallas_util.pack_t``: a ``[64, 64]`` float32 tile
as it stands would be half padding in HBM; the layout is the kernels' own),
and the backward kernel reads it and holds no inverse (8.5 -> 5.1 ms a
layer at the Ling cell's shape): it makes everything else again from the
inputs and returns ``dq``, ``dk`` (of the raw rows under ``norm_qk``),
``dv``, ``dg`` (float32, a channel) and ``d beta``. ``T`` is the one ``[Q,
Q]`` float32 array that reaches HBM.

**The recurrence's kernels** (``hvd_kda_rec_fwd``, ``hvd_kda_rec_bwd``): a
grid cell is a few chunks and a few heads, whose float32 states stay in a
VMEM scratch from a sequence's first chunk to its last, **transposed** ``[V,
K]``: the decay ``Gamma_last`` is a row along the lanes and no ``[K, 1]``
column has to be made. A chunk: ``u = u_own - w S``, ``o = (q Gamma) S +
attn u``, ``S' = Gamma_last S + (k Gamma_last / Gamma)^T u``. The forward
keeps each chunk's entering state in the operand dtype, the backward
kernel's residual; no ``[B, S, H, K, V]`` array exists.

Both pairs sit under **one** ``jax.custom_vjp`` (:func:`_scan`), whose
residuals are the inputs, the five operands, ``T``, the last decays and the
entering states. Decays, running sums, ``T`` and the state are float32; the
other products take operands in ``dtype`` and accumulate in float32.

**What a checkpoint keeps.** The rule's forward (:func:`_scan_forward`) hands
the five operands and ``T`` (``kda_scan_operands``) and the entering states
(``kda_scan_entering``) to ``checkpoint_name`` **as its residuals**, and the
mixer names the output (``kda_scan_out``): every output of both forward
kernels, so the recomputed copy of a block checkpointed under a policy that
keeps the three names (:data:`SAVED_NAMES` and the mixer's) runs neither
``hvd_kda_fwd`` nor ``hvd_kda_rec_fwd``, and the backward kernels read what
the forward pass wrote. ``hvd_kda_rec_fwd`` reads the operands **unnamed**:
``jax.checkpoint`` copies a kept value that the forward pass also reads
through a ``reduce_precision``, behind a Mosaic kernel a read and a write of
every kept byte (PERF.md, Findings, PR 56). Outside a checkpoint a name is
an identity."""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .gated_delta import unit_rows
from .pallas_util import LANES, NORM_EPS, NT, SUBLANES, TN, always, \
    column_as_row, largest_divisor, out_vma, pack_t, raw_row_cotangents, \
    row_sum, t_pack, unit_lower_inverse_in_vmem, unpack_t, use_interpret, \
    varying_like

# The kernels' names in the compiled program and in a device trace; the
# benchmark's readers match ``^hvd_kda_`` (tests/test_program_names.py).
KERNEL_FWD = "hvd_kda_fwd"
KERNEL_BWD = "hvd_kda_bwd"
KERNEL_REC_FWD = "hvd_kda_rec_fwd"
KERNEL_REC_BWD = "hvd_kda_rec_bwd"
# What this module hands ``checkpoint_name``, for a ``jax.checkpoint`` around
# the caller to keep: what the recurrence's backward kernel reads beside the
# scan's inputs. ``hvd_kda_fwd``'s six outputs (``kda_scan_operands``: a head
# a token V in float32 and 3 K + Q in the compute dtype, 369 MB a layer in
# the Ling cell, and for ``hvd_kda_bwd`` alone T, Q more in float32, 67 MB a
# layer, PR 68) and each chunk's entering state, ``hvd_kda_rec_fwd``'s side
# output (``kda_scan_entering``: K V in the compute dtype a head a chunk, 134
# MB a layer). With the mixer's ``kda_scan_out`` kept too the recomputed copy
# runs neither forward kernel (PERF.md, Findings, PR 64). The last decays (2
# MB a layer) are made again from ``g``, which the block makes again anyway.
SAVED_NAMES = ("kda_scan_operands", "kda_scan_entering")
MAX_EXPONENT = 80.0  # exp of it is finite in float32 and in bfloat16
_HI = lax.Precision.HIGHEST
_MAX_CHUNKS = 4    # chunks a grid cell walks, at most
_REC_HEADS = 8     # heads a grid cell of the recurrence holds, at most
_REC_VMEM = 64 << 20
_STATE_DTYPE = jnp.float32  # the recurrence's carried state and its cotangent


def _check(q, k, v, g, beta):
    if q.shape != k.shape or g.shape != k.shape \
            or v.shape[:3] != k.shape[:3] or beta.shape != k.shape[:3]:
        raise ValueError(
            "Kimi delta attention: q, k and g [B, S, H, K], v [B, S, H, V], "
            f"beta [B, S, H]; got {q.shape}, {k.shape}, {v.shape}, "
            f"{g.shape}, {beta.shape}")


def kda_sequential(q, k, v, g, beta, initial_state=None, *,
                   norm_qk: bool = False):
    """The recurrence one token a step, float32: what the tests hold the
    rest to, not a path to train on. ``q``, ``k`` ``[B, S, H, K]``; ``v``
    ``[B, S, H, V]``; ``g`` (log decay, <= 0, a key channel) ``[B, S, H,
    K]``; ``beta`` ``[B, S, H]``. Under ``norm_qk`` ``q`` and ``k`` come raw
    and are L2-normalised a head here, ``q`` over the root of ``K`` besides.
    Returns ``(o [B, S, H, V], state [B, H, K, V])``."""
    _check(q, k, v, g, beta)
    f32 = jnp.float32
    if norm_qk:
        q, k = unit_rows(q, q.shape[-1] ** -0.5), unit_rows(k)
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.sum(state * k_t[..., None], axis=-2)          # S^T k
        state = state + k_t[..., None] \
            * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    start = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[3:], f32) \
        if initial_state is None else initial_state.astype(f32)
    final, o = lax.scan(step, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), final


def _tiling(kernel, key_dim, width, chunk, sub_chunk):
    """Compiled for the TPU, a shape the kernels do not tile raises here, by
    name."""
    if not use_interpret() and (key_dim % LANES or width % LANES
                                or chunk % SUBLANES or sub_chunk % SUBLANES):
        raise ValueError(
            f"{kernel} does not tile chunk={chunk}, sub_chunk={sub_chunk}, "
            f"key_dim={key_dim}, value_dim={width}: it needs a chunk and a "
            f"sub-block that are multiples of {SUBLANES} and heads that are "
            f"multiples of {LANES} lanes (a head of another size is not "
            "carried padded, as the gated delta rule's is)")


class _Chunk:
    """What both chunk-local kernels make of one chunk of one head, all
    float32 unless said: the rows of ``q`` and ``k`` (normed in float32 and
    rounded to the operand dtype once under ``q_scale``), the running sums
    ``cum`` of the log decays, ``beta`` as a column, ``Gamma`` (``grown``)
    and ``Gamma_last / Gamma`` (``to_end``), the decayed ``K K^T`` and ``Q
    K^T`` by sub-blocks (``blocks`` keeps each row sub-block's scales and
    scaled operands for the backward pass) and ``A``. (``T = (I + A)^-1``
    is the forward kernel's to make and the backward's to read.)"""

    def __init__(self, q_ref, k_ref, g_ref, beta_ref, at, head, q_scale,
                 sub: int):
        f32 = jnp.float32
        q, k = q_ref[0, at, :], k_ref[0, at, :]
        self.dtype = dtype = q.dtype
        self.raw, self.q_scale = (q, k), q_scale
        qf, kf = q.astype(f32), k.astype(f32)
        if q_scale is not None:
            self.inv = (lax.rsqrt(row_sum(qf * qf) + NORM_EPS),
                        lax.rsqrt(row_sum(kf * kf) + NORM_EPS))
            qf = (qf * self.inv[0] * q_scale).astype(dtype).astype(f32)
            kf = (kf * self.inv[1]).astype(dtype).astype(f32)
        self.qf, self.kf = qf, kf
        size = q.shape[0]
        rows = lax.broadcasted_iota(jnp.int32, (size, size), 0)
        cols = lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.lower, self.strictly = rows >= cols, rows > cols
        self.diagonal, self.upper = rows == cols, rows <= cols
        self.cum = cum = jnp.dot(
            jnp.where(self.lower, 1.0, 0.0).astype(f32), g_ref[0, at, :],
            precision=_HI, preferred_element_type=f32)
        block = beta_ref[0, at, :]
        lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
        self.beta = row_sum(jnp.where(lane == head, block, 0.0))
        self.grown = jnp.exp(cum)
        self.to_end = jnp.exp(cum[size - 1:size, :] - cum)
        self.blocks, kk, qk = [], [], []
        for i in range(size // sub):
            at_i = slice(i * sub, (i + 1) * sub)
            ref = cum[i * sub:i * sub + 1, :]
            row_scale = jnp.exp(cum[at_i] - ref)
            col_scale = jnp.exp(jnp.minimum(ref - cum, MAX_EXPONENT))
            qr = (qf[at_i] * row_scale).astype(dtype)
            kr = (kf[at_i] * row_scale).astype(dtype)
            kc = (kf * col_scale).astype(dtype)
            self.blocks.append((at_i, row_scale, col_scale, qr, kr, kc))
            kk.append(lax.dot_general(kr, kc, NT, preferred_element_type=f32))
            qk.append(lax.dot_general(qr, kc, NT, preferred_element_type=f32))
        self.kk = jnp.concatenate(kk, axis=0)
        self.qk = jnp.where(self.lower, jnp.concatenate(qk, axis=0), 0.0)
        self.a = jnp.where(self.strictly, self.kk * self.beta, 0.0)

    def raw_cotangents(self, dq, dk):
        """The float32 cotangents of the rows as they came, for those of the
        normed ones: for ``n = t r``, ``r = rsqrt(|t|^2 + eps)``, ``dt = r
        (dn - n <dn, n>)``, ``q``'s times its scale."""
        if self.q_scale is None:
            return dq, dk
        return raw_row_cotangents(self.raw, self.inv, (dq, dk),
                                  (self.q_scale, 1.0))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, u_ref, w_ref, attn_ref,
                qin_ref, kout_ref, t_ref, *, nc: int, chunk: int, sub: int,
                q_scale):
    """A grid cell: ``nc`` chunks of one sequence, one head. What the
    recurrence reads, in its order ``[c, B, H, Q, .]``, and the float32
    ``T`` for the backward kernel (``pallas_util.pack_t``)."""
    f32, dtype = jnp.float32, q_ref.dtype
    head = pl.program_id(2)

    @always
    def _chunks():
        def one(n, carry):
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            c = _Chunk(q_ref, k_ref, g_ref, beta_ref, at, head, q_scale, sub)
            t = unit_lower_inverse_in_vmem(c.a)
            t_ref[n, 0, 0] = pack_t(t)
            t = t.astype(dtype)
            v = v_ref[0, at, :].astype(f32)
            u_ref[n, 0, 0] = jnp.dot(t, (v * c.beta).astype(dtype),
                                     preferred_element_type=f32)
            w_ref[n, 0, 0] = jnp.dot(
                t, (c.kf * (c.beta * c.grown)).astype(dtype),
                preferred_element_type=f32).astype(dtype)
            attn_ref[n, 0, 0] = c.qk.astype(dtype)
            qin_ref[n, 0, 0] = (c.qf * c.grown).astype(dtype)
            kout_ref[n, 0, 0] = (c.kf * c.to_end).astype(dtype)
            return carry

        lax.fori_loop(0, nc, one, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, du_ref, dw_ref,
                dattn_ref, dqin_ref, dkout_ref, dlast_ref, t_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, dbeta_ref, *, nc: int, chunk: int,
                sub: int, q_scale):
    """The forward's cotangents on the same grid cell. Everything but ``T``
    is made again from the inputs, and ``T`` is read as the forward kernel
    wrote it (no inverse here); ``dT = du (beta V)^T + dw (beta K Gamma)^T``,
    ``dA = -T^T dT T^T``; the decayed products' cotangents go back through
    each row sub-block's scaled operands, and a running sum's cotangent is
    a row's ``<d row, row>`` less a column's. ``dlast`` is the cotangent of
    ``Gamma_last`` (the recurrence's), ``dg`` the running sums' turned
    round: a product with the upper triangle of ones."""
    f32, dtype = jnp.float32, q_ref.dtype
    head = pl.program_id(2)

    @always
    def _chunks():
        def one(n, carry):
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            c = _Chunk(q_ref, k_ref, g_ref, beta_ref, at, head, q_scale, sub)
            t32 = unpack_t(t_ref[n, 0, 0], chunk)
            t = t32.astype(dtype)
            v = v_ref[0, at, :].astype(f32)
            du = du_ref[n, 0, 0].astype(dtype)
            dw = dw_ref[n, 0, 0]
            written = c.beta * c.grown
            dt = lax.dot_general(du, (v * c.beta).astype(dtype), NT,
                                 preferred_element_type=f32) \
                + lax.dot_general(dw, (c.kf * written).astype(dtype), NT,
                                  preferred_element_type=f32)
            dvb = lax.dot_general(t, du, TN, preferred_element_type=f32)
            dkb = lax.dot_general(t, dw, TN, preferred_element_type=f32)
            dv_ref[0, at, :] = (dvb * c.beta).astype(dv_ref.dtype)
            through_k = dkb * c.kf
            dbeta = row_sum(dvb * v) + row_sum(through_k * c.grown)
            dk = dkb * written
            dcum = through_k * written
            da = -lax.dot_general(
                lax.dot_general(t32, dt, TN, precision=_HI,
                                preferred_element_type=f32),
                t32, NT, precision=_HI, preferred_element_type=f32)
            da = jnp.where(c.strictly, da, 0.0)
            dbeta = dbeta + row_sum(da * c.kk)
            dkk = (da * c.beta).astype(dtype)
            dqk = jnp.where(c.lower, dattn_ref[n, 0, 0], 0).astype(dtype)
            dq_rows, dk_rows = [], []
            dk_cols = jnp.zeros(c.kf.shape, f32)
            for at_i, row_scale, col_scale, qr, kr, kc in c.blocks:
                dkk_i, dqk_i = dkk[at_i], dqk[at_i]
                dk_rows.append(jnp.dot(
                    dkk_i, kc, preferred_element_type=f32) * row_scale)
                dq_rows.append(jnp.dot(
                    dqk_i, kc, preferred_element_type=f32) * row_scale)
                dk_cols = dk_cols + col_scale * (
                    lax.dot_general(dkk_i, kr, TN,
                                    preferred_element_type=f32)
                    + lax.dot_general(dqk_i, qr, TN,
                                      preferred_element_type=f32))
            dq_rows = jnp.concatenate(dq_rows, axis=0)
            dk_rows = jnp.concatenate(dk_rows, axis=0)
            dqin = dqin_ref[n, 0, 0].astype(f32)
            moved = dkout_ref[n, 0, 0].astype(f32) * c.to_end
            dq = dq_rows + dqin * c.grown
            dk = dk + dk_rows + dk_cols + moved
            moved = moved * c.kf
            dcum = dcum + (dk_rows - dk_cols) * c.kf \
                + (dq_rows + dqin * c.grown) * c.qf - moved
            last = jnp.sum(moved, axis=0, keepdims=True) \
                + dlast_ref[0, pl.ds(n, 1), 0, :] * c.grown[chunk - 1:chunk]
            dg_ref[0, at, :] = jnp.dot(
                jnp.where(c.upper, 1.0, 0.0).astype(f32), dcum,
                precision=_HI, preferred_element_type=f32) + last
            dq, dk = c.raw_cotangents(dq, dk)
            dq_ref[0, at, :] = dq.astype(dq_ref.dtype)
            dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
            dbeta_ref[0, n, 0] = column_as_row(c.diagonal, dbeta)
            return carry

        lax.fori_loop(0, nc, one, 0)


def _plan(kernel, body, q, v, chunk, sub, q_scale):
    """What both chunk-local calls share: the block specs by name on the
    grid ``(batch, block of chunks, head)`` and ``pallas_call``'s other
    arguments. Operands stay as the mixer has them, tokens by channels: a
    head's chunk is a ``[Q, K]`` block of ``q``, ``k`` and ``g`` ``[B, S, H
    K]`` and a ``[Q, V]`` block of ``v``; ``beta`` ``[B, S, H]`` comes whole
    and a head's column is picked by a masked sum along the lanes. ``scan``
    is a tensor in the recurrence's order ``[c, B, H, Q, .]`` (``scan_t``:
    the kept ``T``, ``pallas_util.pack_t``), ``last`` a
    chunk's row a head ``[B, c, 1, H K]``, ``rows`` the backward's ``d
    beta`` ``[B, c, H, 1, Q]``."""
    batch, seq, heads, key_dim = q.shape
    width = v.shape[3]
    n_chunks = seq // chunk
    nc = largest_divisor(n_chunks, _MAX_CHUNKS)
    _tiling(kernel, key_dim, width, chunk, sub)

    def tokens(lanes, walk=True):
        return pl.BlockSpec((1, nc * chunk, lanes),
                            lambda b, c, h: (b, c, h if walk else 0))

    def scan(last, rows=chunk):
        return pl.BlockSpec((nc, 1, 1, rows, last),
                            lambda b, c, h: (c, b, h, 0, 0))

    pack = t_pack(chunk)

    specs = {"key": tokens(key_dim), "value": tokens(width),
             "heads": tokens(heads, walk=False),
             "last": pl.BlockSpec((1, nc, 1, key_dim),
                                  lambda b, c, h: (b, c, 0, h)),
             "rows": pl.BlockSpec((1, nc, 1, 1, chunk),
                                  lambda b, c, h: (b, c, h, 0, 0)),
             "scan_k": scan(key_dim), "scan_v": scan(width),
             "scan_q": scan(chunk),
             "scan_t": scan(pack * chunk, chunk // pack)}
    call = dict(
        grid=(batch, n_chunks // nc, heads),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=use_interpret(), name=kernel)
    body = functools.partial(body, nc=nc, chunk=chunk, sub=sub,
                             q_scale=q_scale)
    return specs, body, call


_FWD_SPECS = ("key", "key", "value", "key", "heads")
_SCAN_SPECS = ("scan_v", "scan_k", "scan_q", "scan_k", "scan_k")


def _flat(t):
    """``[B, S, H, X]`` -> ``[B, S, H X]``."""
    return t.reshape(t.shape[:2] + (-1,))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "sub", "q_scale"))
def _fwd_call(q, k, v, g, beta, *, chunk, sub, q_scale):
    """``q``, ``k`` ``[B, S, H, K]`` and ``v`` ``[B, S, H, V]`` in the
    operand dtype, ``S`` a whole number of chunks; float32 ``g`` ``[B, S, H,
    K]`` and ``beta`` ``[B, S, H]`` -> ``u_own = T (beta V)`` float32, ``w =
    T (beta K Gamma)``, ``attn``, ``q Gamma`` and ``k Gamma_last / Gamma``
    in the operand dtype, ``[c, B, H, Q, .]``, and last the float32 ``T``
    for :func:`_bwd_call` alone, ``[c, B, H, Q / 2, 2 Q]`` at a chunk of 64
    (``pallas_util.pack_t``)."""
    specs, body, call = _plan(KERNEL_FWD, _fwd_kernel, q, v, chunk, sub,
                              q_scale)
    args = (_flat(q), _flat(k), _flat(v), _flat(g), beta)
    batch, seq, heads, key_dim = q.shape
    lead, pack = (seq // chunk, batch, heads), t_pack(chunk)
    vma = out_vma(*args)
    return pl.pallas_call(
        body, in_specs=[specs[name] for name in _FWD_SPECS],
        out_specs=[specs[name] for name in _SCAN_SPECS + ("scan_t",)],
        out_shape=[jax.ShapeDtypeStruct(lead + last, dtype, vma=vma)
                   for last, dtype in (
                       ((chunk, v.shape[3]), jnp.float32),
                       ((chunk, key_dim), q.dtype), ((chunk, chunk), q.dtype),
                       ((chunk, key_dim), q.dtype),
                       ((chunk, key_dim), q.dtype),
                       ((chunk // pack, pack * chunk), jnp.float32))],
        **call)(*args)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "sub", "q_scale"))
def _bwd_call(q, k, v, g, beta, du, dw, dattn, dqin, dkout, dlast, t, *,
              chunk, sub, q_scale):
    """The cotangents of :func:`_fwd_call`'s inputs for those of its first
    five outputs and of the chunks' last decays (``dlast`` ``[B, c, 1, H
    K]``), given its sixth (``t``: the forward's ``T``, as written): ``dq``,
    ``dk``, ``dv`` in the operand dtype, ``dg`` and ``d beta`` float32."""
    specs, body, call = _plan(KERNEL_BWD, _bwd_kernel, q, v, chunk, sub,
                              q_scale)
    args = (_flat(q), _flat(k), _flat(v), _flat(g), beta, du, dw, dattn,
            dqin, dkout, dlast, t)
    batch, seq, heads, _ = q.shape
    vma = out_vma(*args)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        body,
        in_specs=[specs[name] for name in _FWD_SPECS + _SCAN_SPECS
                  + ("last", "scan_t")],
        out_specs=[specs[name] for name in ("key", "key", "value", "key",
                                            "rows")],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in args[:4]]
        + [jax.ShapeDtypeStruct((batch, seq // chunk, heads, 1, chunk),
                                jnp.float32, vma=vma)], **call)(*args)
    # [B, c, H, 1, Q] -> [B, S, H]
    dbeta = dbeta[:, :, :, 0].swapaxes(2, 3).reshape(beta.shape)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


def _rec_fwd_kernel(u_ref, w_ref, attn_ref, qin_ref, kout_ref, decay_ref,
                    start_ref, o_ref, final_ref, enter_ref, state, *, nc: int,
                    hb: int, chunk: int, key_dim: int, width: int):
    """A grid cell: ``nc`` chunks of one sequence and ``hb`` heads, whose
    float32 states, transposed ``[hb, V, K]``, stay in the scratch from a
    sequence's first block of chunks to its last (the grid's last axis, in
    order). A chunk: ``u = u_own - w S``, ``o = (q Gamma) S + attn u``, ``S'
    = Gamma_last S + (k Gamma_last / Gamma)^T u``; each chunk's entering
    state is kept in the operand dtype."""
    f32, dtype = jnp.float32, w_ref.dtype
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _start():
        state[...] = start_ref[0].astype(state.dtype)

    @always
    def _chunks():
        def one(n, carry):
            rows = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            for r in range(hb):
                entering = state[r].astype(f32)
                s = entering.astype(dtype)
                enter_ref[n, 0, r] = s
                on_s = lax.dot_general(
                    jnp.concatenate([w_ref[n, 0, r], qin_ref[n, 0, r]]), s,
                    NT, preferred_element_type=f32)              # [2Q, V]
                u = (u_ref[n, 0, r] - on_s[:chunk]).astype(dtype)
                o_ref[0, rows, r * width:(r + 1) * width] = (
                    on_s[chunk:] + jnp.dot(
                        attn_ref[n, 0, r], u,
                        preferred_element_type=f32)).astype(o_ref.dtype)
                decay = decay_ref[0, pl.ds(n, 1), 0,
                                  r * key_dim:(r + 1) * key_dim]   # [1, K]
                state[r] = (decay * entering + lax.dot_general(
                    u, kout_ref[n, 0, r], TN,
                    preferred_element_type=f32)).astype(state.dtype)
            return carry

        lax.fori_loop(0, nc, one, 0)

    @pl.when(block == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = state[...].astype(f32)


def _rec_bwd_kernel(u_ref, w_ref, attn_ref, qin_ref, kout_ref, decay_ref,
                    enter_ref, do_ref, dfinal_ref, du_ref, dw_ref, dattn_ref,
                    dqin_ref, dkout_ref, ddecay_ref, dstart_ref, dstate, *,
                    nc: int, hb: int, chunk: int, key_dim: int, width: int):
    """The forward's grid cell, blocks of chunks and the chunks inside one
    walked from the last to the first. The scratch carries ``dS`` (float32,
    transposed as the state is) from the final state's cotangent to the
    initial state's. A chunk makes ``u`` again from its kept entering state
    ``S`` and, with ``dS'`` the cotangent of the state it left: ``du =
    attn^T do + (k Gamma_last / Gamma) dS'``, ``d attn = do u^T``, ``d (k
    Gamma_last / Gamma) = u dS'^T``, ``d (q Gamma) = do S^T``, ``dw = -du
    S^T``, ``d Gamma_last = diag(dS' S^T)`` and ``dS = Gamma_last dS' + (q
    Gamma)^T do - w^T du``."""
    f32, dtype = jnp.float32, w_ref.dtype
    block, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(block == 0)
    def _start():
        dstate[...] = dfinal_ref[0].astype(dstate.dtype)

    @always
    def _chunks():
        def one(i, carry):
            n = nc - 1 - i
            rows = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            for r in range(hb):
                lanes = slice(r * key_dim, (r + 1) * key_dim)
                s = enter_ref[n, 0, r]                           # [V, K]
                left = dstate[r].astype(f32)
                w, qin = w_ref[n, 0, r], qin_ref[n, 0, r]
                do = do_ref[0, rows, r * width:(r + 1) * width]
                ds_out = left.astype(dtype)
                u = (u_ref[n, 0, r] - lax.dot_general(
                    w, s, NT, preferred_element_type=f32)).astype(dtype)
                du = lax.dot_general(attn_ref[n, 0, r], do, TN,
                                     preferred_element_type=f32) \
                    + lax.dot_general(kout_ref[n, 0, r], ds_out, NT,
                                      preferred_element_type=f32)
                du_ref[n, 0, r] = du
                du = du.astype(dtype)
                dattn_ref[n, 0, r] = lax.dot_general(
                    do, u, NT, preferred_element_type=f32).astype(dtype)
                dkout_ref[n, 0, r] = jnp.dot(
                    u, ds_out, preferred_element_type=f32).astype(dtype)
                dqin_ref[n, 0, r] = jnp.dot(
                    do, s, preferred_element_type=f32).astype(dtype)
                dw_ref[n, 0, r] = -jnp.dot(
                    du, s, preferred_element_type=f32).astype(dtype)
                ddecay_ref[0, pl.ds(n, 1), 0, lanes] = jnp.sum(
                    left * s.astype(f32), axis=0, keepdims=True)
                dstate[r] = (
                    decay_ref[0, pl.ds(n, 1), 0, lanes] * left
                    + lax.dot_general(do, qin, TN,
                                      preferred_element_type=f32)
                    - lax.dot_general(du, w, TN, preferred_element_type=f32)
                ).astype(dstate.dtype)
            return carry

        lax.fori_loop(0, nc, one, 0)

    @pl.when(block == last)
    def _final():
        dstart_ref[0] = dstate[...].astype(f32)


def _rec_plan(kernel, body, u_own, w, sub, backward: bool):
    """What the recurrence's two calls share: the block specs by name on the
    grid ``(batch, block of heads, block of chunks)``, the last axis in order
    (backward, the index maps read block ``last - c``). ``scan`` is a tensor
    in the recurrence's order ``[c, B, H, Q, .]``, ``entering`` the kept
    states ``[c, B, H, V, K]``, ``tokens`` the output or its cotangent ``[B,
    S, H V]``, ``state`` an initial or final state ``[B, H, V, K]`` and
    ``decay`` the chunks' last decays ``[B, c, 1, H K]``."""
    n_chunks, batch, heads, chunk, width = u_own.shape
    key_dim = w.shape[-1]
    nc = largest_divisor(n_chunks, _MAX_CHUNKS)
    hb = largest_divisor(heads, _REC_HEADS)
    _tiling(kernel, key_dim, width, chunk, sub)
    blocks = n_chunks // nc

    def at(c):
        return blocks - 1 - c if backward else c

    def scan(*last):
        return pl.BlockSpec((nc, 1, hb) + last,
                            lambda b, h, c: (at(c), b, h, 0, 0))

    specs = {
        "scan_v": scan(chunk, width), "scan_k": scan(chunk, key_dim),
        "scan_q": scan(chunk, chunk), "entering": scan(width, key_dim),
        "tokens": pl.BlockSpec((1, nc * chunk, hb * width),
                               lambda b, h, c: (b, at(c), h)),
        "state": pl.BlockSpec((1, hb, width, key_dim),
                              lambda b, h, c: (b, h, 0, 0)),
        "decay": pl.BlockSpec((1, nc, 1, hb * key_dim),
                              lambda b, h, c: (b, at(c), 0, h))}
    call = dict(
        grid=(batch, heads // hb, blocks),
        scratch_shapes=[pltpu.VMEM((hb, width, key_dim), _STATE_DTYPE)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_REC_VMEM),
        interpret=use_interpret(), name=kernel)
    body = functools.partial(body, nc=nc, hb=hb, chunk=chunk,
                             key_dim=key_dim, width=width)
    return specs, body, call


_REC_SPECS = _SCAN_SPECS + ("decay",)


@functools.partial(jax.jit, inline=True, static_argnames="sub")
def _rec_fwd_call(u_own, w, attn, q_in, k_out, decay, start, *, sub):
    """The chunk-local kernels' outputs ``[c, B, H, Q, .]``, the chunks'
    last decays ``[B, c, 1, H K]`` and the float32 state a sequence starts
    from, transposed ``[B, H, V, K]`` -> ``o`` ``[B, S, H V]`` in the
    operand dtype, the float32 state after the last chunk (transposed) and
    each chunk's entering state ``[c, B, H, V, K]`` in the operand dtype."""
    specs, body, call = _rec_plan(KERNEL_REC_FWD, _rec_fwd_kernel, u_own, w,
                                  sub, backward=False)
    args = (u_own, w, attn, q_in, k_out, decay, start)
    vma = out_vma(*args)
    n_chunks, batch, heads, chunk, width = u_own.shape
    out = [("tokens", (batch, n_chunks * chunk, heads * width), w.dtype),
           ("state", start.shape, jnp.float32),
           ("entering", (n_chunks, batch) + start.shape[1:], w.dtype)]
    return pl.pallas_call(
        body, in_specs=[specs[name] for name in _REC_SPECS + ("state",)],
        out_specs=[specs[name] for name, _, _ in out],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype, vma=vma)
                   for _, shape, dtype in out], **call)(*args)


@functools.partial(jax.jit, inline=True, static_argnames="sub")
def _rec_bwd_call(u_own, w, attn, q_in, k_out, decay, entering, do, dfinal,
                  *, sub):
    """The cotangents of :func:`_rec_fwd_call`'s inputs for those of ``o``
    and the final state, the operands' own shapes and dtypes."""
    specs, body, call = _rec_plan(KERNEL_REC_BWD, _rec_bwd_kernel, u_own, w,
                                  sub, backward=True)
    args = (u_own, w, attn, q_in, k_out, decay, entering, do, dfinal)
    vma = out_vma(*args)
    return pl.pallas_call(
        body,
        in_specs=[specs[name] for name in _REC_SPECS + (
            "entering", "tokens", "state")],
        out_specs=[specs[name] for name in _REC_SPECS + ("state",)],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in (u_own, w, attn, q_in, k_out, decay, dfinal)],
        **call)(*args)


def _last_decays(g, chunk):
    """``Gamma_last`` of every chunk, ``[B, c, 1, H K]`` float32."""
    batch, seq = g.shape[:2]
    return jnp.exp(jnp.sum(g.reshape(batch, seq // chunk, chunk, 1, -1),
                           axis=2))


def _scan_forward(static, q, k, v, g, beta, start):
    chunk, sub, q_scale = static
    *operands, t = _fwd_call(q, k, v, g, beta, chunk=chunk, sub=sub,
                             q_scale=q_scale)
    decay = _last_decays(g, chunk)
    o, final, entering = _rec_fwd_call(*operands, decay, start, sub=sub)
    # Named as residuals only: the kernel above read the operands unnamed
    # and nothing of the forward pass reads T, so the forward pass reads no
    # kept value (the module docstring).
    kept = tuple(checkpoint_name(x, "kda_scan_operands")
                 for x in (*operands, t))
    return (o, final), (q, k, v, g, beta, *kept, decay,
                        checkpoint_name(entering, "kda_scan_entering"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(static, q, k, v, g, beta, start):
    """The chunked form through the four kernels, one rule: ``(o [B, S, H
    V], the final state [B, H, V, K])`` of ``q``, ``k`` ``[B, S, H, K]``,
    ``v``, float32 ``g`` and ``beta`` and the transposed initial state;
    ``static`` is ``(chunk, sub_chunk, q_scale)``."""
    return _scan_forward(static, q, k, v, g, beta, start)[0]


def _scan_bwd(static, kept, cotangents):
    chunk, sub, q_scale = static
    q, k, v, g, beta, *operands, t, decay, entering = kept
    *d_operands, d_decay, d_start = _rec_bwd_call(
        *operands, decay, entering, *cotangents, sub=sub)
    # Gamma_last = exp(c_last): the kernel is handed the cotangent of the
    # decay itself and multiplies by Gamma_last where it has it.
    return (*_bwd_call(q, k, v, g, beta, *d_operands, d_decay, t,
                       chunk=chunk, sub=sub, q_scale=q_scale), d_start)


_scan.defvjp(_scan_forward, _scan_bwd)


def kda_chunked(q, k, v, g, beta, chunk: int = 64, *, sub_chunk: int = 16,
                lower_bound: float = -5.0, dtype: Any = jnp.bfloat16,
                initial_state=None, norm_qk: bool = False):
    """The recurrence in chunks of ``chunk`` tokens (a power of two) whose
    decayed products are made in sub-blocks of ``sub_chunk`` rows. Arguments
    as :func:`kda_sequential`; every ``g`` lies in ``[lower_bound, 0]`` (the
    caller's gate is bounded below: that is what lets ``k / Gamma`` be taken
    inside a sub-block; a bound whose ``|lower_bound| (sub_chunk - 1)``
    passes :data:`MAX_EXPONENT` raises). ``dtype`` is the MXU operands'
    type. Under ``norm_qk`` the chunk-local kernels norm the rows of ``q``
    and ``k`` (``q`` times ``K^-0.5`` besides) and the gradient comes back
    for the raw rows. Returns ``(o [B, S, H, V] in dtype, the float32 state
    after the last token [B, H, K, V])``. A length the chunk does not divide
    is padded with tokens that neither decay nor write."""
    _check(q, k, v, g, beta)
    sub_chunk = min(sub_chunk, chunk)
    if chunk < 1 or chunk & (chunk - 1) or chunk % sub_chunk:
        raise ValueError(
            "Kimi delta attention: chunk must be a power of two and a whole "
            f"number of sub-blocks, got chunk={chunk}, sub_chunk={sub_chunk}")
    if not -MAX_EXPONENT <= lower_bound * (sub_chunk - 1) <= 0:
        raise ValueError(
            f"Kimi delta attention: a gate bounded below at {lower_bound} "
            f"over a sub-block of {sub_chunk} tokens reaches exp("
            f"{-lower_bound * (sub_chunk - 1)}), beyond exp({MAX_EXPONENT}): "
            "take a smaller sub_chunk or a tighter bound")
    batch, seq, heads, key_dim = k.shape
    width = v.shape[3]
    f32 = jnp.float32
    pad = (-seq) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    runtime.note_traced(
        "hvdtpu_spmd_kda_traces_total", heads=heads, key_dim=key_dim,
        value_dim=width, chunk=chunk, sub_chunk=sub_chunk,
        lower_bound=lower_bound)
    start = jnp.zeros((batch, heads, width, key_dim), f32) \
        if initial_state is None \
        else initial_state.astype(f32).swapaxes(-1, -2)
    o, final = _scan(
        (chunk, sub_chunk, key_dim ** -0.5 if norm_qk else None),
        q.astype(dtype), k.astype(dtype), v.astype(dtype), g.astype(f32),
        beta.astype(f32), varying_like(start, q))
    o = o.reshape(batch, seq + pad, heads, width)[:, :seq]
    return o, final.swapaxes(-1, -2)
