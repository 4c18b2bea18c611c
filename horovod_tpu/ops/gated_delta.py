"""The gated delta rule: a linear-attention recurrence whose state is
*corrected* by each token, not only added to. The chunked form is four
Pallas kernels (below): two for the part that needs no state, two for the
recurrence over chunks, which keep a head's state in VMEM from a sequence's
first chunk to its last. The plain forms the tests hold them to are
``jax.numpy``.

A value head keeps a state ``S`` in ``R^{K x V}`` (keys by values), zero at
the start of a sequence. With ``alpha_t = exp(g_t)`` in (0, 1] the decay and
``beta_t`` in [0, 2] the writing strength::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(Yang, Kautz and Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464, as
remembered; there is no network here.) The token first reads what the decayed
state already answers for its key, and writes only the difference: with
``beta = 0`` the state only decays, with ``alpha = 1`` it is the ungated
delta rule. A token's transition ``alpha (I - beta k k^T)`` has, for a unit
key, the eigenvalue ``alpha (1 - beta)`` along it: in [0, 1) for ``beta`` in
(0, 1] (a model's ``sigmoid(b)``), in (-1, 1) for ``beta`` in (0, 2) (twice
it: "negative eigenvalues"); the arithmetic is the same, ``A``'s entries
double and ``T`` lies further from the identity. Value head ``h`` of ``Hv``
reads key head ``h // (Hv / Hk)``.
``q`` and ``k`` enter the rule L2-normalised a head, ``q`` over the root of
the head's size besides. **Where the norm is made** is the static argument
``norm_qk`` of both forms: without it the caller made it and the rows come
normed; with it (what ``models/decoder/mixers/gdn.py`` passes, and nothing
else) the rows come raw, as the mixer's convolution wrote them, and the
chunk-local kernels make it in VMEM: a row ``t`` of a key head's ``[Q, K]``
block becomes ``t * rsqrt(sum(t * t) + 1e-6)`` in float32 (``q`` times
``K^-0.5`` for the head's true ``K``), rounded to the operand dtype once,
before ``K K^T`` and ``Q K^T``, and the backward kernel, which makes the
normed rows again, turns its float32 ``dq`` and ``dk`` into the raw rows'
cotangents, ``dt = r (dn - n <dn, n>)`` for ``n = t r``, before their one
rounding. No float32 copy of ``q`` or ``k`` and no head-shaped one reaches
HBM (PERF.md, Findings, PR 50). :func:`unit_rows` is the plain line.

:func:`gated_delta_sequential` is that recurrence one token a step, float32:
what the tests hold the rest to, not a path to train on.

:func:`gated_delta_chunked` computes the same in chunks of ``chunk`` tokens
(the WY form). Write ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)`` for what
token ``t`` really writes, so that ``S_t = alpha_t S_{t-1} + k_t u_t^T``, and
``G_t`` for the product of the chunk's decays up to ``t``. For a chunk that
enters with state ``S``::

    (I + A) U = beta V - (beta G K) S,
        A_{tj} = beta_t (G_t / G_j) <k_t, k_j>  for j < t, else 0
    T = (I + A)^{-1}           unit lower triangular, [chunk, chunk]
    U = T (beta V) - (T (beta G K)) S
    o_t = G_t S^T q_t + sum_{j <= t} (G_t / G_j) <q_t, k_j> u_j
    S' = G_last S + sum_j (G_last / G_j) k_j u_j^T

``T`` and the two products it is applied to need no state and are made for
all chunks at once; the recurrence then carries ``S`` through the chunks in
order, three lines a chunk. Decays, running sums, ``T`` and the state are
float32; the other products take operands in ``dtype`` and accumulate in
float32, as ``ops/ssd.py::ssd_chunked`` does.

**The chunk-local kernels** (``hvd_gdn_fwd``, ``hvd_gdn_bwd``, one
``jax.custom_vjp``: :func:`_chunk_local`) compute everything with two
chunk-length axes: the rows' norms under ``norm_qk``, ``K
K^T`` and ``Q K^T`` (once a key head), the masked decay tile ``exp(cum_i -
cum_j)``, ``A``, ``T`` (float32 throughout, rounded to ``dtype`` once before
it is applied), ``u_own = T (beta V)`` (float32), ``w = T (beta G K)``,
``attn = (Q K^T) * decay`` and the two scaled copies the recurrence reads,
``q G`` and ``k G_last / G``. The one ``[Q, Q]`` float32 tensor that
reaches HBM is ``T`` itself (below). The operands stay as the mixer has
them, tokens by channels: a grid cell is ``chunks_per_block`` chunks of one
sequence (walked in a loop), one key head (a ``[Q, K]`` block of ``q`` and
of ``k`` a chunk) and the value heads that read it (their ``[Q, V]`` blocks
side by side in ``v``); the float32 running sums ``cum`` and ``beta`` come
as ``[B, S, Hv]`` and a head's column is picked by a masked sum along the
lanes. The outputs are written in the recurrence's order, ``[c, B, Hv, Q,
.]``. **The inverse is made once a step**: the forward kernel writes the
float32 ``T`` it holds, before its rounding, as a sixth output, and the
backward kernel reads it and holds no inverse (PR 68; the inverse was 4.8
of that kernel's 12.0 ms a layer at the Qwen cell's shape). In HBM a
chunk's ``T`` lies with its lower 32 rows beside its upper 32, ``[c, B, Hv,
32, 128]`` at a chunk of 64 (``pallas_util.pack_t``: as it stands a ``[64,
64]`` float32 tile would be half padding there); the layout is the kernels'
own and nothing else reads it. The backward kernel takes the forward's
inputs and that ``T`` as its only residuals, makes the normed rows, the
decays and ``A`` again in VMEM, forms ``dT`` from the cotangents of
``u_own`` and ``w``, applies ``dA = -T^T dT T^T`` (float32, the highest
precision) and returns ``dq`` and ``dk`` (summed over the key head's value
heads), ``dv`` (rounded once to ``dtype``), ``d cum`` and ``d beta``
(float32, a row a head ``[B, c, Hk, 2 Hv / Hk, Q]``, turned back outside).
Off the TPU the kernels run in Pallas interpret mode; on it a chunk they do
not tile raises (:func:`_tiling`). The five operands cross HBM to the
recurrence, whose residuals they are, and with ``T`` **are what a
checkpoint keeps**: the recurrence's rule names each of the five
``gdn_scan_operands`` (:data:`SAVED_NAMES`) and the chunk-local rule's
forward names ``T`` the same, as its residual alone, so the recomputed copy
of a block checkpointed under a policy that keeps the name does not run
``hvd_gdn_fwd`` (a value head a token the lanes' ``V`` and ``Q`` in float32
and ``3 K + Q`` in ``dtype``: 872 MB a layer in the Qwen cell, 134 of them
``T``; the five were in HBM already and the backward kernels read them from
there either way). Outside a checkpoint a name is an identity.

**Heads of any size.** A key head of ``K`` and a value head of ``V`` come
and go as published; the four kernels carry a head at the next multiple of
the lane width (``K`` 96 -> 128, ``V`` 192 -> 256: Mosaic's blocks are whole
lane tiles), the one place that knows being :func:`gated_delta_chunked`,
which pads ``q``, ``k`` (raw under ``norm_qk``: zeros add nothing to a row's
sum of squares, so the norm over 128 lanes is the norm over 96), ``v`` and
the initial state with zeros (``pallas_util.to_lanes``) and drops the padded
columns of ``o`` and rows and
columns of the final state. Zeros change no product: ``K K^T`` and ``Q K^T``
sum over the key lanes, the padded columns of ``u``, ``w``, ``q G`` and ``k
G_last / G`` are zero, so the padded rows and columns of the state stay zero
through the decay, the correction and the update, and the padded columns of
``o`` are exactly zero (the tests hold that). At lane multiples nothing is
padded and the program is the same. Everything between the kernels
(``u_own``, ``w``, the kept entering states) is held at the padded sizes; the
same code runs in interpret mode. ``hvdtpu_spmd_gdn_kernel_traces_total``'s
``key_lanes`` and ``value_lanes`` say what a head occupies.

**The recurrence's kernels** (``hvd_gdn_rec_fwd``, ``hvd_gdn_rec_bwd``, a
second ``jax.custom_vjp``: :func:`_recurrence`) read those outputs from HBM,
a chunk's last decay a head and the state a sequence starts from. A grid
cell is ``_REC_CHUNKS`` chunks of one sequence (a loop) and ``_REC_HEADS``
value heads, whose float32 states ``[K, V]`` sit side by side in a VMEM
scratch; the grid's last axis walks a sequence's blocks of chunks in order.
A chunk: ``u = u_own - w S``, ``o = (q G) S + attn u``, ``S' = G_last S + (k
G_last / G)^T u`` (two products: ``w`` and ``q G`` stacked over ``S``,
``attn`` and the transposed ``k G_last / G`` stacked over ``u``), and ``o``
goes straight to its place in ``[B, S, Hv V]``. The forward pass runs the
kernel with those two outputs; the rule's forward also keeps each chunk's
entering state in ``dtype`` ``[c, B, Hv, K, V]``, the backward kernel's only
residual beside the inputs. That one turns the last axis round (the index
maps read block ``last - c``), carries ``dS`` float32 from the final state's
cotangent to the initial state's, makes ``u`` again and returns the
cotangents of the five operands (what ``hvd_gdn_bwd`` takes) and of the
last decays. Both are bound by their bytes on a v5e (PERF.md, Findings, PR
36: the kernels with their products taken out take as long). **The entering
states are named for a checkpoint too** (``gdn_scan_entering``, in the
rule's forward) **where no lane of them is
padding**: ``K`` and ``V`` both multiples of the lane width, which
:func:`gated_delta_chunked` sees beside the carried sizes and hands
:func:`_recurrence` as its one static argument. With them and the five
operands kept every output of ``hvd_gdn_rec_fwd`` is, and a checkpointed
block runs it once a step; a head carried with zeros (96 x 192 on 128 x
256: 44% of a kept state would be zeros) names none and its block makes the
states again, from the kept operands. **Which values the forward kernel
reads follows from that** (:func:`_recurrence_fwd`): ``jax.checkpoint``
copies a kept value that the forward pass reads too through a
``reduce_precision``, which behind a kernel is a pass over every kept byte,
so where the states are kept the kernel reads the operands unnamed and only
the residuals carry the name, and where they are not it reads them named,
or the recomputed copy would want ``hvd_gdn_fwd`` for them (PERF.md,
Findings, PR 56).

**The inverse in VMEM** (``pallas_util.unit_lower_inverse_in_vmem``, which
``hvd_kda_fwd`` shares; the forward kernels alone call it since PR 68): the
diagonal blocks of
``_SUBSTITUTE`` = 32 rows by forward substitution on the vector unit (a
column a step, exact float32), then :func:`_inverse`'s rounds from there up:
at a chunk of 64, one round of two ``[64, 64]`` products on the MXU at the
highest precision. Timed on the v5e at the benchmark cell's shape (PERF.md,
Findings, PR 34): every round a product 14.0 ms a layer a pass, blocks of 8
/ 16 / 32 by substitution 9.9 / 7.9 / 7.1, substitution alone 8.4.

**Which inverse, and why by blocks.** :func:`unit_lower_inverse` is the
plain form the tests hold the kernels' ``T`` to. It inverts ``I + A`` by
blocks:
the inverse of ``[[M11, 0], [M21, M22]]`` is ``[[M11^-1, 0], [-M22^-1 M21
M11^-1, M22^-1]]``, from 1 x 1 blocks up, doubling: ``log2(chunk) - 1``
rounds of two batched float32 products on the whole ``[chunk, chunk]``
matrices (the blocks taken out one size at a time cost more in gathers than
the masked products do in operations). It is forward substitution a block at
a time and as stable; the product ``prod_j (I + (-A)^(2^j))`` of the nilpotent
series is not (equal keys at ``beta = alpha = 1`` make ``A`` all ones below
the diagonal, whose powers hold binomials of 1e17 that must cancel). Its
backward pass is its own (``dA = -T^T dT T^T``) and keeps ``T`` alone.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from .pallas_util import LANES, NEG_INF, NORM_EPS as _NORM_EPS, NT, \
    SUBLANES, TN, always, column_as_row, largest_divisor, \
    out_vma as _out_vma, pack_t, raw_row_cotangents, row_sum as _row_sum, \
    t_pack, to_lanes, unit_lower_inverse_in_vmem, unpack_t, \
    use_interpret as _use_interpret, varying_like

# The kernels' names in the compiled program and in a device trace; the
# benchmark's readers match ``^hvd_gdn_`` (tests/test_program_names.py).
KERNEL_FWD = "hvd_gdn_fwd"
KERNEL_BWD = "hvd_gdn_bwd"
KERNEL_REC_FWD = "hvd_gdn_rec_fwd"
KERNEL_REC_BWD = "hvd_gdn_rec_bwd"
# What this module hands ``checkpoint_name``, for a ``jax.checkpoint`` around
# the caller to keep: what the recurrence's backward kernels read beside
# their inputs. The chunk-local kernel's six outputs (``gdn_scan_operands``:
# a value head a token the lanes' V in float32 and 3 K + Q in the compute
# dtype, which cross HBM to the recurrence's kernels in the forward pass
# already, and for ``hvd_gdn_bwd`` alone T, Q more in float32, PR 68: 738 +
# 134 MB a layer in the Qwen cell, 472 + 63 in the Olmo cell) and each
# chunk's entering state (``gdn_scan_entering``, 268 MB a layer in the Qwen
# cell), named only where no lane of a state is padding (key and value head
# both whole lane tiles; computed here from the shapes, no caller's option).
# With both kept the recomputed copy runs neither ``hvd_gdn_fwd`` nor
# ``hvd_gdn_rec_fwd``; with the five alone, the Olmo cell's 96 x 192 heads,
# it runs the second (PERF.md, Findings, PR 56). The decays and the states
# of a head that is carried padded stay recomputed.
SAVED_NAMES = ("gdn_scan_operands", "gdn_scan_entering")
_HI = lax.Precision.HIGHEST
_MAX_CHUNKS = 4   # chunks a grid cell, at most
_SUBSTITUTE = 32  # rows of the inverse's diagonal blocks made by substitution
_REC_HEADS = 8    # value heads a grid cell of the recurrence, at most
_REC_CHUNKS = 4   # chunks a grid cell of the recurrence walks, at most
_REC_VMEM = 64 << 20  # the recurrence's kernels' limit, of a v5e's 128 MiB:
                      # half for the blocks in flight (``_rec_heads``)


def _check(q, k, v, g, beta):
    if q.shape != k.shape or v.shape[2] % k.shape[2] \
            or g.shape != v.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            "gated delta rule: q and k [B, S, Hk, K], v [B, S, Hv, V] with "
            "Hk dividing Hv, g and beta [B, S, Hv]; got "
            f"{q.shape}, {k.shape}, {v.shape}, {g.shape}, {beta.shape}")


def unit_rows(t, scale: float = 1.0):
    """``t`` L2-normalised along its last axis in float32, times ``scale``:
    ``t * rsqrt(sum(t * t) + 1e-6) * scale``. The plain line the kernels'
    norm is held to (``norm_qk``); a zero row stays zero."""
    t = t.astype(jnp.float32)
    return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                         + _NORM_EPS) * scale


def gated_delta_sequential(q, k, v, g, beta, initial_state=None, *,
                           norm_qk: bool = False):
    """The recurrence one token a step, float32. ``q``, ``k`` ``[B, S, Hk,
    K]``; ``v`` ``[B, S, Hv, V]``; ``g`` (log decay, <= 0) and ``beta``
    ``[B, S, Hv]``. Under ``norm_qk`` ``q`` and ``k`` come raw and are
    L2-normalised a head here, ``q`` over the root of ``K`` besides
    (:func:`unit_rows`), as :func:`gated_delta_chunked`'s kernels do.
    Returns ``(o [B, S, Hv, V], state [B, Hv, K, V])``."""
    _check(q, k, v, g, beta)
    f32 = jnp.float32
    rep = v.shape[2] // k.shape[2]
    if norm_qk:
        q, k = unit_rows(q, q.shape[-1] ** -0.5), unit_rows(k)
    q, k = (jnp.repeat(t.astype(f32), rep, axis=2) for t in (q, k))
    v, g, beta = (t.astype(f32) for t in (v, g, beta))

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.sum(state * k_t[..., None], axis=-2)          # S^T k
        state = state + k_t[..., None] \
            * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    start = jnp.zeros(v.shape[:1] + v.shape[2:3] + k.shape[3:] + v.shape[3:],
                      f32) if initial_state is None \
        else initial_state.astype(f32)
    final, o = lax.scan(step, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), final


def _inverse(a):
    """The inverse by blocks on whole matrices: ``D_b``, block diagonal with
    the inverses of the ``b x b`` diagonal blocks, gives ``D_2b = D_b - D_b
    L_b D_b`` with ``L_b`` the lower left ``b x b`` block of every ``2b x
    2b`` diagonal block of ``a`` (the product is ``M22^-1 M21 M11^-1`` there
    and zero elsewhere). ``D_1 = I``, so the first round is a mask.
    (:func:`unit_lower_inverse`'s body, and with it the reference for
    ``pallas_util.unit_lower_inverse_in_vmem``: no program path has called it
    since PR 34.)"""
    size = a.shape[-1]
    rows, cols = np.arange(size)[:, None], np.arange(size)[None, :]

    def lower_left(b):
        return (rows // (2 * b) == cols // (2 * b)) \
            & (rows // b % 2 == 1) & (cols // b % 2 == 0)

    inv = jnp.eye(size, dtype=a.dtype) - jnp.where(lower_left(1), a, 0)
    b = 2
    while b < size:
        inv = inv - jnp.einsum(
            "...ij,...jk,...kl->...il", inv, jnp.where(lower_left(b), a, 0),
            inv, precision=_HI)
        b *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^{-1}`` for ``a`` ``[..., n, n]`` strictly lower triangular
    (what lies on or above the diagonal is not read), ``n`` a power of two,
    in ``a``'s type: the module docstring's inverse by blocks. The reference
    the tests hold ``pallas_util.unit_lower_inverse_in_vmem`` (the kernels'
    ``T``) to; no
    program path has called it since PR 34."""
    return _inverse(a)


def _inverse_fwd(a):
    inv = _inverse(a)
    return inv, inv


def _inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.einsum("...ij,...jk,...kl->...il", t, g, t,
                        precision=_HI),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunks_per_block(n_chunks: int) -> int:
    """Chunks a grid cell of the chunk-local kernels walks."""
    return largest_divisor(n_chunks, _MAX_CHUNKS)


def _tiling(kernel, key_dim, width, chunk, heads_per_block, dtype):
    """Compiled for the TPU, a shape the kernels do not tile raises here, by
    name (``key_dim`` and ``width`` are what a head occupies in the kernels:
    :func:`gated_delta_chunked` pads a head of any size to whole lane tiles,
    so of its callers only a chunk can be refused); and, trace time only,
    the record of a call's tiling behind ``hvd.metrics()``."""
    if not _use_interpret() and (
            key_dim % LANES or width % LANES or chunk % SUBLANES):
        raise ValueError(
            f"{kernel} does not tile chunk={chunk}, key_lanes={key_dim}, "
            f"value_lanes={width}: it needs a chunk that is a multiple of "
            f"{SUBLANES} and heads carried at multiples of {LANES} lanes "
            "(gated_delta_chunked pads a head of any size to that)")
    runtime.note_traced(
        "hvdtpu_spmd_gdn_kernel_traces_total", kernel=kernel, chunk=chunk,
        heads_per_block=heads_per_block, operand_dtype=jnp.dtype(dtype).name,
        key_lanes=key_dim, value_lanes=width)


class _Chunk:
    """What both kernels make of one chunk of one key head: ``q``, ``k`` in
    the operand dtype and float32, ``K K^T`` and ``Q K^T`` (float32, made
    once for the key head's value heads) and the two triangular masks.
    With ``q_scale`` (``norm_qk``) the blocks come raw: a row is normed in
    float32, ``t * rsqrt(sum(t * t) + eps)``, ``q`` times ``q_scale``
    besides, and rounded to the operand dtype once, before the products;
    the rows' ``rsqrt`` stay for :meth:`raw_cotangents`."""

    def __init__(self, q_ref, k_ref, at, q_scale):
        f32 = jnp.float32
        self.q, self.k = q_ref[0, at, :], k_ref[0, at, :]
        self.raw, self.q_scale = (self.q, self.k), q_scale
        if q_scale is not None:
            dtype = self.q.dtype
            q, k = self.q.astype(f32), self.k.astype(f32)
            self.inv = (lax.rsqrt(_row_sum(q * q) + _NORM_EPS),
                        lax.rsqrt(_row_sum(k * k) + _NORM_EPS))
            self.q = (q * self.inv[0] * q_scale).astype(dtype)
            self.k = (k * self.inv[1]).astype(dtype)
        self.qf, self.kf = self.q.astype(f32), self.k.astype(f32)
        self.kk = lax.dot_general(self.k, self.k, NT,
                                  preferred_element_type=f32)
        self.qk = lax.dot_general(self.q, self.k, NT,
                                  preferred_element_type=f32)
        size = self.q.shape[0]
        rows = lax.broadcasted_iota(jnp.int32, (size, size), 0)
        cols = lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.lower, self.strictly = rows >= cols, rows > cols
        self.diagonal = rows == cols

    def raw_cotangents(self, dq, dk):
        """The float32 cotangents of the blocks as they came, for those of
        the normed ones: for a row ``n = t r``, ``r = rsqrt(|t|^2 + eps)``,
        ``dt = r (dn - n <dn, n>)``, ``q``'s times its scale. The
        cotangents themselves where the caller normed."""
        if self.q_scale is None:
            return dq, dk
        return raw_row_cotangents(self.raw, self.inv, (dq, dk),
                                  (self.q_scale, 1.0))

    def column(self, block, head):
        """Column ``head`` (a traced index) of ``block`` ``[Q, Hv]``, ``[Q,
        1]``: Mosaic slices no lane at an index it cannot see."""
        lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
        return _row_sum(jnp.where(lane == head, block, 0.0))

    def as_row(self, column):
        """A column ``[Q, 1]`` as a row ``[1, Q]``, through the diagonal."""
        return column_as_row(self.diagonal, column)

    def head(self, cum_ref, beta_ref, at, head):
        """Value head ``head``'s float32 parts: the running sums as a column
        ``[Q, 1]`` and ``beta`` likewise, ``exp(cum_i - cum_j)`` kept where
        ``j <= i`` (the mask on the exponent: above the diagonal the
        difference is positive and may overflow), ``exp(cum)`` and
        ``exp(cum_last - cum)`` as columns, and ``A``. (``T = (I + A)^-1``
        is the forward kernel's to make and the backward's to read.)"""
        cum = self.column(cum_ref[0, at, :], head)
        beta = self.column(beta_ref[0, at, :], head)
        decay = jnp.exp(jnp.where(self.lower, cum - self.as_row(cum),
                                  NEG_INF))
        a = jnp.where(self.strictly, self.kk * decay * beta, 0.0)
        size = cum.shape[0]
        return beta, decay, jnp.exp(cum), \
            jnp.exp(cum[size - 1:size, :] - cum), a


def _fwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, u_ref, w_ref,
                attn_ref, qin_ref, kout_ref, t_ref, *, nc: int, rep: int,
                chunk: int, width: int, q_scale):
    """A grid cell: ``nc`` chunks of one sequence, one key head and its
    ``rep`` value heads. ``u = T (beta V)`` float32, ``w = T (beta G K)``,
    ``attn = (Q K^T) * decay``, ``q G`` and ``k G_last / G``: what the
    recurrence over chunks reads, in its order ``[c, B, Hv, Q, .]``; and the
    float32 ``T`` itself, for the backward kernel (``pallas_util.pack_t``)."""
    f32, dtype = jnp.float32, q_ref.dtype
    first = pl.program_id(2) * rep

    @always
    def _chunks():
        def one(n, carry):
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            c = _Chunk(q_ref, k_ref, at, q_scale)
            for r in range(rep):
                beta, decay, grown, to_end, a = c.head(
                    cum_ref, beta_ref, at, first + r)
                t = unit_lower_inverse_in_vmem(a, _SUBSTITUTE)
                t_ref[n, 0, r] = pack_t(t)
                t = t.astype(dtype)
                v = v_ref[0, at, r * width:(r + 1) * width].astype(f32)
                u_ref[n, 0, r] = jnp.dot(t, (v * beta).astype(dtype),
                                         preferred_element_type=f32)
                w_ref[n, 0, r] = jnp.dot(
                    t, (c.kf * (beta * grown)).astype(dtype),
                    preferred_element_type=f32).astype(dtype)
                attn_ref[n, 0, r] = (c.qk * decay).astype(dtype)
                qin_ref[n, 0, r] = (c.qf * grown).astype(dtype)
                kout_ref[n, 0, r] = (c.kf * to_end).astype(dtype)
            return carry

        lax.fori_loop(0, nc, one, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, du_ref, dw_ref,
                dattn_ref, dqin_ref, dkout_ref, t_ref, dq_ref, dk_ref, dv_ref,
                drows_ref, *, nc: int, rep: int, chunk: int, width: int,
                q_scale):
    """The forward's cotangents on the same grid cell. ``A`` is made again
    from the inputs and ``T`` is read as the forward kernel wrote it (no
    inverse here); ``dT = du (beta V)^T + dw (beta G K)^T``,
    ``dA = -T^T dT T^T`` (float32, the highest precision), and from ``dA``
    and ``d attn`` the cotangents of ``K K^T`` and ``Q K^T`` (summed over
    the key head's value heads here, then through their products into
    ``dq`` and ``dk``), of ``beta`` and of the running sums: ``M = dA * A +
    d attn * attn`` summed along its rows for ``d cum_i`` and down its
    columns against ``d cum_j``. ``drows`` holds a row a head of ``d cum``
    and then a row a head of ``d beta``."""
    f32, dtype = jnp.float32, q_ref.dtype
    first = pl.program_id(2) * rep

    @always
    def _chunks():
        row_at = lax.broadcasted_iota(jnp.int32, (2 * rep, chunk), 0)
        is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1

        def one(n, carry):
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            c = _Chunk(q_ref, k_ref, at, q_scale)
            dkk = dqk = jnp.zeros((chunk, chunk), f32)
            dq = dk = jnp.zeros(c.qf.shape, f32)
            drows = jnp.zeros((2 * rep, chunk), f32)
            for r in range(rep):
                beta, decay, grown, to_end, a = c.head(
                    cum_ref, beta_ref, at, first + r)
                t32 = unpack_t(t_ref[n, 0, r], chunk)
                t = t32.astype(dtype)
                lanes = slice(r * width, (r + 1) * width)
                v = v_ref[0, at, lanes].astype(f32)
                du = du_ref[n, 0, r].astype(dtype)
                dw = dw_ref[n, 0, r]
                written = beta * grown
                dt = lax.dot_general(du, (v * beta).astype(dtype), NT,
                                     preferred_element_type=f32) \
                    + lax.dot_general(dw, (c.kf * written).astype(dtype),
                                      NT, preferred_element_type=f32)
                dvb = lax.dot_general(t, du, TN, preferred_element_type=f32)
                dkb = lax.dot_general(t, dw, TN, preferred_element_type=f32)
                dv_ref[0, at, lanes] = (dvb * beta).astype(dv_ref.dtype)
                through_k = _row_sum(dkb * c.kf)
                dk = dk + dkb * written
                dbeta = _row_sum(dvb * v) + through_k * grown
                dcum = through_k * written
                # dA = -T^T dT T^T, kept strictly below the diagonal.
                da = -lax.dot_general(
                    lax.dot_general(t32, dt, TN, precision=_HI,
                                    preferred_element_type=f32),
                    t32, NT, precision=_HI, preferred_element_type=f32)
                weighted = jnp.where(c.strictly, da, 0.0) * decay
                dkk = dkk + weighted * beta
                dbeta = dbeta + _row_sum(weighted * c.kk)
                dattn = dattn_ref[n, 0, r].astype(f32)
                dqk = dqk + dattn * decay
                m = da * a + dattn * (c.qk * decay)
                dqin = dqin_ref[n, 0, r].astype(f32)
                dq = dq + dqin * grown
                dkout = dkout_ref[n, 0, r].astype(f32)
                dk = dk + dkout * to_end
                to_last = _row_sum(dkout * c.kf) * to_end
                dcum = dcum + _row_sum(m) + _row_sum(dqin * c.qf) * grown \
                    - to_last + jnp.where(
                        is_last, jnp.sum(to_last, axis=0, keepdims=True), 0.0)
                drows = jnp.where(
                    row_at == r,
                    c.as_row(dcum) - jnp.sum(m, axis=0, keepdims=True), drows)
                drows = jnp.where(row_at == rep + r, c.as_row(dbeta), drows)
            dkk, dqk = dkk.astype(dtype), dqk.astype(dtype)
            dk = dk + jnp.dot(dkk, c.k, preferred_element_type=f32) \
                + lax.dot_general(dkk, c.k, TN, preferred_element_type=f32) \
                + lax.dot_general(dqk, c.q, TN, preferred_element_type=f32)
            dq = dq + jnp.dot(dqk, c.k, preferred_element_type=f32)
            dq, dk = c.raw_cotangents(dq, dk)
            dq_ref[0, at, :] = dq.astype(dq_ref.dtype)
            dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
            drows_ref[0, n, 0] = drows
            return carry

        lax.fori_loop(0, nc, one, 0)


def _plan(kernel, body, q, k, v, cum, beta, q_scale):
    """What both calls share: the operands as the kernels read them, the
    block specs by name on the grid ``(batch, block of chunks, key head)``,
    and ``pallas_call``'s other arguments. Everything stays as the mixer
    has it, tokens by channels: ``q``, ``k`` ``[B, S, Hk K]`` (a key head's
    chunk is a ``[Q, K]`` block), ``v`` ``[B, S, Hv V]``, the float32
    running sums and ``beta`` ``[B, S, Hv]`` (every head's, a chunk's rows:
    fetched once for a block of chunks, the key heads walk it). ``scan`` is
    a tensor in the recurrence's order ``[c, B, Hv, Q, .]`` (``scan_t``: the
    kept ``T``, ``pallas_util.pack_t``), ``rows`` the
    backward's ``d cum | d beta`` ``[B, c, Hk, 2 rep, Q]``. ``q_scale``:
    what the kernels multiply the ``q`` they normed by, None where the
    caller normed."""
    batch, seq, key_heads, key_dim = q.shape
    heads, width = v.shape[2:]
    n_chunks, chunk = cum.shape[1:3]
    nc, rep = chunks_per_block(n_chunks), heads // key_heads
    _tiling(kernel, key_dim, width, chunk, rep, q.dtype)
    args = (q.reshape(batch, seq, -1), k.reshape(batch, seq, -1),
            v.reshape(batch, seq, -1), cum.reshape(batch, seq, heads),
            beta.reshape(batch, seq, heads))

    def tokens(lanes, walk=True):
        return pl.BlockSpec((1, nc * chunk, lanes),
                            lambda b, c, h: (b, c, h if walk else 0))

    def scan(last, rows=chunk):
        return pl.BlockSpec((nc, 1, rep, rows, last),
                            lambda b, c, h: (c, b, h, 0, 0))

    pack = t_pack(chunk)
    specs = {"key": tokens(key_dim), "value": tokens(rep * width),
             "heads": tokens(heads, walk=False),
             "rows": pl.BlockSpec((1, nc, 1, 2 * rep, chunk),
                                  lambda b, c, h: (b, c, h, 0, 0)),
             "scan_k": scan(key_dim), "scan_v": scan(width),
             "scan_q": scan(chunk),
             "scan_t": scan(pack * chunk, chunk // pack)}
    call = dict(
        grid=(batch, n_chunks // nc, key_heads),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_use_interpret(), name=kernel)
    body = functools.partial(body, nc=nc, rep=rep, chunk=chunk, width=width,
                             q_scale=q_scale)
    return args, specs, body, call


_FWD_SPECS = ("key", "key", "value", "heads", "heads")
_SCAN_SPECS = ("scan_v", "scan_k", "scan_q", "scan_k", "scan_k")


def _scan_shapes(q, v, cum, vma):
    """``u`` (float32), ``w``, ``attn``, ``q G``, ``k G_last / G`` in the
    recurrence's order, and the kept float32 ``T`` in the same."""
    chunk = cum.shape[2]
    lead, pack = (cum.shape[1], q.shape[0], v.shape[2]), t_pack(chunk)
    return [jax.ShapeDtypeStruct(lead + last, dtype, vma=vma)
            for last, dtype in (
                ((chunk, v.shape[3]), jnp.float32),
                ((chunk, q.shape[3]), q.dtype), ((chunk, chunk), q.dtype),
                ((chunk, q.shape[3]), q.dtype),
                ((chunk, q.shape[3]), q.dtype),
                ((chunk // pack, pack * chunk), jnp.float32))]


@functools.partial(jax.jit, inline=True, static_argnames="q_scale")
def _fwd_call(q, k, v, cum, beta, *, q_scale=None):
    """(Jitted inline, as :func:`_bwd_call` is: a kernel's body, this one's
    1,100 equations, most of them unrolled substitution, is traced once for
    a shape, and a block's recomputed copy and the next layers re-bind it
    under their own scopes; 0.4 s of a job's set-up on the benchmark's
    host.)

    ``q``, ``k`` ``[B, S, Hk, K]`` and ``v`` ``[B, S, Hv, V]`` in the
    operand dtype, ``S`` a whole number of chunks; float32 ``cum`` (the
    running sum of the log decays inside each chunk) and ``beta`` ``[B, c,
    Q, Hv]`` -> what needs no state, ``[c, B, Hv, Q, .]``: ``u_own = T (beta
    V)`` float32, ``w = T (beta G K)``, ``attn = (Q K^T) * decay``, ``q G``
    and ``k G_last / G`` in the operand dtype, and last the float32 ``T``
    for :func:`_bwd_call` alone, ``[c, B, Hv, Q / 2, 2 Q]`` at a chunk of 64
    (``pallas_util.pack_t``). With ``q_scale`` the kernel
    norms the rows of ``q`` and ``k`` first (:class:`_Chunk`)."""
    args, specs, body, call = _plan(KERNEL_FWD, _fwd_kernel, q, k, v, cum,
                                    beta, q_scale)
    return pl.pallas_call(
        body, in_specs=[specs[name] for name in _FWD_SPECS],
        out_specs=[specs[name] for name in _SCAN_SPECS + ("scan_t",)],
        out_shape=_scan_shapes(q, v, cum, _out_vma(*args)), **call)(*args)


@functools.partial(jax.jit, inline=True, static_argnames="q_scale")
def _bwd_call(q, k, v, cum, beta, du, dw, dattn, dqin, dkout, t, *,
              q_scale=None):
    """The cotangents of :func:`_fwd_call`'s inputs for those of its first
    five outputs, given its sixth (``t``: the forward's ``T``, as written):
    ``dq``, ``dk`` (of the raw rows under ``q_scale``: through the
    norm in float32), ``dv`` in the operand dtype (rounded once), ``d cum``
    and ``d beta`` float32."""
    args, specs, body, call = _plan(KERNEL_BWD, _bwd_kernel, q, k, v, cum,
                                    beta, q_scale)
    args += (du, dw, dattn, dqin, dkout, t)
    vma = _out_vma(*args)
    rep = v.shape[2] // q.shape[2]
    n_chunks, chunk = cum.shape[1:3]
    dq, dk, dv, drows = pl.pallas_call(
        body, in_specs=[specs[name] for name in _FWD_SPECS + _SCAN_SPECS
                        + ("scan_t",)],
        out_specs=[specs[name] for name in ("key", "key", "value", "rows")],
        out_shape=[jax.ShapeDtypeStruct(t.shape, q.dtype, vma=vma)
                   for t in args[:3]]
        + [jax.ShapeDtypeStruct(
            (q.shape[0], n_chunks, q.shape[2], 2 * rep, chunk), jnp.float32,
            vma=vma)], **call)(*args)
    # [B, c, Hk, cum | beta, rep, Q] -> two of [B, c, Q, Hv]
    drows = drows.reshape(drows.shape[:3] + (2, rep, chunk))
    dcum, dbeta = (drows[:, :, :, i].transpose(0, 1, 4, 2, 3)
                   .reshape(cum.shape) for i in range(2))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dcum, dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunk_local(q_scale, q, k, v, cum, beta):
    """The WY form's part that needs no state, through the kernels: see
    :func:`_fwd_call` (its first five outputs: the recurrence reads no
    ``T``)."""
    return tuple(_fwd_call(q, k, v, cum, beta, q_scale=q_scale)[:5])


def _chunk_local_fwd(q_scale, *inputs):
    # The residuals: the inputs (the backward kernel makes the normed rows
    # and A again in VMEM) and the forward kernel's T, which it reads and
    # makes no inverse. T is named for a checkpoint as the kernel's other
    # outputs are (``SAVED_NAMES``), here and as a residual only: no value
    # of the forward pass reads it, so no ``reduce_precision`` pass lands
    # on it, and a recomputed copy that finds all six kept runs no
    # ``hvd_gdn_fwd``.
    *operands, t = _fwd_call(*inputs, q_scale=q_scale)
    return tuple(operands), inputs + (
        checkpoint_name(t, "gdn_scan_operands"),)


def _chunk_local_bwd(q_scale, kept, cotangents):
    *inputs, t = kept
    return _bwd_call(*inputs, *cotangents, t, q_scale=q_scale)


_chunk_local.defvjp(_chunk_local_fwd, _chunk_local_bwd)


def _along(row, r: int, lanes: int):
    """Lane ``r`` of ``row`` ``[1, X]`` along ``lanes`` lanes, ``[1,
    lanes]``. (Through a select: Mosaic broadcasts a ``[1, 1]`` along one
    axis at a time, and two plain broadcasts fold into one.)"""
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return jnp.where(lane >= 0, row[:, r:r + 1], 0.0)


def _rec_fwd_kernel(u_ref, w_ref, attn_ref, qin_ref, kout_ref, decay_ref,
                    start_ref, o_ref, final_ref, *rest, nc: int, hb: int,
                    chunk: int, width: int):
    """A grid cell: ``nc`` chunks of one sequence and ``hb`` value heads,
    whose float32 states ``[hb, K, V]`` stay in the scratch from a
    sequence's first block of chunks to its last (the grid's last axis, in
    order). A chunk: ``u = u_own - w S``, ``o = (q G) S + attn u``, ``S' =
    G_last S + (k G_last / G)^T u``; ``o`` goes to its place in ``[B, S, Hv
    V]``. With a third output (the rule's forward) each chunk's entering
    state is kept in the operand dtype."""
    f32, dtype = jnp.float32, w_ref.dtype
    enter_ref, state = rest if len(rest) == 2 else (None,) + rest
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _start():
        state[...] = start_ref[0]

    @always
    def _chunks():
        def one(n, carry):
            rows = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            decays = decay_ref[0, 0, pl.ds(block * nc + n, 1), :]  # [1, hb]
            for r in range(hb):
                entering = state[r]
                s = entering.astype(dtype)
                if enter_ref is not None:
                    enter_ref[n, 0, r] = s
                # Two products a chunk, not four: w and q G stacked over S,
                # attn and (k G_last / G)^T stacked over u (a product's cost
                # here is loading the operand that stays).
                on_s = jnp.dot(
                    jnp.concatenate([w_ref[n, 0, r], qin_ref[n, 0, r]]), s,
                    preferred_element_type=f32)
                u = (u_ref[n, 0, r] - on_s[:chunk]).astype(dtype)
                on_u = jnp.dot(
                    jnp.concatenate([attn_ref[n, 0, r],
                                     kout_ref[n, 0, r].T]), u,
                    preferred_element_type=f32)
                o_ref[0, rows, r * width:(r + 1) * width] = \
                    (on_s[chunk:] + on_u[:chunk]).astype(o_ref.dtype)
                state[r] = _along(decays, r, width) * entering + on_u[chunk:]
            return carry

        lax.fori_loop(0, nc, one, 0)

    @pl.when(block == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = state[...]


def _rec_bwd_kernel(u_ref, w_ref, attn_ref, qin_ref, kout_ref, decay_ref,
                    enter_ref, do_ref, dfinal_ref, du_ref, dw_ref, dattn_ref,
                    dqin_ref, dkout_ref, ddecay_ref, dstart_ref, dstate, *,
                    nc: int, hb: int, chunk: int, width: int):
    """The forward's grid cell, the blocks of chunks and the chunks inside
    one walked from the last to the first (the index maps turn the grid's
    last axis round). The scratch carries ``dS`` float32 from the final
    state's cotangent to the initial state's. A chunk makes ``u`` again from
    its kept entering state ``S`` and, with ``dS'`` the cotangent of the
    state it left: ``du = attn^T do + (k G_last / G) dS'``, ``d attn = do
    u^T``, ``d (k G_last / G) = u dS'^T``, ``d (q G) = do S^T``, ``dw = -du
    S^T``, ``d G_last = <dS', S>`` and ``dS = G_last dS' + (q G)^T do - w^T
    du``."""
    f32, dtype = jnp.float32, w_ref.dtype
    block, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(block == 0)
    def _start():
        dstate[...] = dfinal_ref[0]

    @always
    def _chunks():
        lane = lax.broadcasted_iota(jnp.int32, (1, hb), 1)

        def one(i, carry):
            n = nc - 1 - i
            at = pl.ds((last - block) * nc + n, 1)
            rows = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            decays = decay_ref[0, 0, at, :]                      # [1, hb]
            ddecays = jnp.zeros((1, hb), f32)
            for r in range(hb):
                s, left = enter_ref[n, 0, r], dstate[r]
                w, qin = w_ref[n, 0, r], qin_ref[n, 0, r]
                do = do_ref[0, rows, r * width:(r + 1) * width]
                ds_out = left.astype(dtype)
                u = (u_ref[n, 0, r] - jnp.dot(
                    w, s, preferred_element_type=f32)).astype(dtype)
                du = lax.dot_general(attn_ref[n, 0, r], do, TN,
                                     preferred_element_type=f32) \
                    + jnp.dot(kout_ref[n, 0, r], ds_out,
                              preferred_element_type=f32)
                du_ref[n, 0, r] = du
                du = du.astype(dtype)
                dattn_ref[n, 0, r] = lax.dot_general(
                    do, u, NT, preferred_element_type=f32).astype(dtype)
                dkout_ref[n, 0, r] = lax.dot_general(
                    u, ds_out, NT, preferred_element_type=f32).astype(dtype)
                dqin_ref[n, 0, r] = lax.dot_general(
                    do, s, NT, preferred_element_type=f32).astype(dtype)
                dw_ref[n, 0, r] = -lax.dot_general(
                    du, s, NT, preferred_element_type=f32).astype(dtype)
                ddecays = jnp.where(
                    lane == r, jnp.sum(_row_sum(left * s.astype(f32)),
                                       axis=0, keepdims=True), ddecays)
                dstate[r] = _along(decays, r, width) * left \
                    + lax.dot_general(qin, do, TN,
                                      preferred_element_type=f32) \
                    - lax.dot_general(w, du, TN, preferred_element_type=f32)
            ddecay_ref[0, 0, at, :] = ddecays
            return carry

        lax.fori_loop(0, nc, one, 0)

    @pl.when(block == last)
    def _final():
        dstart_ref[0] = dstate[...]


def _rec_heads(heads, nc, chunk, key_dim, width, itemsize) -> int:
    """Value heads a grid cell of the recurrence holds: the most that divide
    ``heads``, ``_REC_HEADS`` at most, whose blocks in flight (the backward
    call's, the larger: every operand and cotangent of ``nc`` chunks, the
    kept entering states, the states' cotangents and the scratch; each block
    twice, Pallas fetches the next while one is worked on) take half of
    ``_REC_VMEM`` at most; the other half is the kernel's own values."""
    def blocks(hb):
        scan = nc * hb * chunk * (
            2 * 4 * width + 2 * itemsize * (3 * key_dim + chunk))
        states = hb * key_dim * width * (nc * itemsize + 3 * 4)
        return scan + states + nc * chunk * hb * width * itemsize

    return next(hb for hb in range(min(_REC_HEADS, heads), 0, -1)
                if heads % hb == 0
                and (hb == 1 or 2 * blocks(hb) <= _REC_VMEM // 2))


def _rec_plan(kernel, body, u_own, w, backward: bool):
    """What the recurrence's two calls share: the tiling, the block specs by
    name on the grid ``(batch, block of value heads, block of chunks)``, the
    last axis in order (backward, the index maps read block ``last - c``),
    and ``pallas_call``'s other arguments. ``scan`` is a tensor in the
    recurrence's order ``[c, B, Hv, Q, .]``, ``entering`` the kept states
    ``[c, B, Hv, K, V]``, ``tokens`` the output or its cotangent ``[B, S, Hv
    V]``, ``state`` an initial or final state ``[B, Hv, K, V]`` and
    ``decay`` a chunk's last decay a head, a grid cell's heads side by side:
    ``[B, Hv / hb, c, hb]`` (:func:`_by_head_block`)."""
    n_chunks, batch, heads, chunk, width = u_own.shape
    key_dim = w.shape[-1]
    nc = largest_divisor(n_chunks, _REC_CHUNKS)
    hb = _rec_heads(heads, nc, chunk, key_dim, width, w.dtype.itemsize)
    _tiling(kernel, key_dim, width, chunk, hb, w.dtype)
    blocks = n_chunks // nc

    def at(c):
        return blocks - 1 - c if backward else c

    def scan(*last):
        return pl.BlockSpec((nc, 1, hb) + last,
                            lambda b, h, c: (at(c), b, h, 0, 0))

    specs = {
        "scan_v": scan(chunk, width), "scan_k": scan(chunk, key_dim),
        "scan_q": scan(chunk, chunk), "entering": scan(key_dim, width),
        "tokens": pl.BlockSpec((1, nc * chunk, hb * width),
                               lambda b, h, c: (b, at(c), h)),
        "state": pl.BlockSpec((1, hb, key_dim, width),
                              lambda b, h, c: (b, h, 0, 0)),
        "decay": pl.BlockSpec((1, 1, n_chunks, hb),
                              lambda b, h, c: (b, h, 0, 0))}
    call = dict(
        grid=(batch, heads // hb, blocks),
        scratch_shapes=[pltpu.VMEM((hb, key_dim, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_REC_VMEM),
        interpret=_use_interpret(), name=kernel)
    body = functools.partial(body, nc=nc, hb=hb, chunk=chunk, width=width)
    return hb, specs, body, call


def _by_head_block(decay, hb):
    """``[B, c, Hv]`` -> ``[B, Hv / hb, c, hb]``: a grid cell's block of it
    is the whole of the last two axes."""
    batch, n_chunks, heads = decay.shape
    return decay.reshape(batch, n_chunks, heads // hb, hb).swapaxes(1, 2)


_REC_SPECS = _SCAN_SPECS + ("decay",)


@functools.partial(jax.jit, inline=True, static_argnames="keep")
def _rec_fwd_call(u_own, w, attn, q_in, k_out, decay, start, *, keep: bool):
    """The chunk-local kernels' outputs ``[c, B, Hv, Q, .]``, a chunk's last
    decay a head ``[B, c, Hv]`` and the float32 state a sequence starts
    from ``[B, Hv, K, V]`` -> ``o`` ``[B, S, Hv V]`` in the operand dtype,
    the float32 state after the last chunk and, with ``keep``, each chunk's
    entering state ``[c, B, Hv, K, V]`` in the operand dtype."""
    hb, specs, body, call = _rec_plan(KERNEL_REC_FWD, _rec_fwd_kernel, u_own,
                                      w, backward=False)
    args = (u_own, w, attn, q_in, k_out, _by_head_block(decay, hb), start)
    vma = _out_vma(*args)
    n_chunks, batch, heads, chunk, width = u_own.shape
    out = [("tokens", (batch, n_chunks * chunk, heads * width), w.dtype),
           ("state", start.shape, jnp.float32)]
    if keep:
        out.append(("entering", (n_chunks, batch) + start.shape[1:],
                    w.dtype))
    return pl.pallas_call(
        body, in_specs=[specs[name] for name in _REC_SPECS + ("state",)],
        out_specs=[specs[name] for name, _, _ in out],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype, vma=vma)
                   for _, shape, dtype in out], **call)(*args)


@functools.partial(jax.jit, inline=True)
def _rec_bwd_call(u_own, w, attn, q_in, k_out, decay, entering, do, dfinal):
    """The cotangents of :func:`_rec_fwd_call`'s inputs for those of ``o``
    and the final state, the operands' own shapes and dtypes."""
    hb, specs, body, call = _rec_plan(KERNEL_REC_BWD, _rec_bwd_kernel, u_own,
                                      w, backward=True)
    by_block = _by_head_block(decay, hb)
    args = (u_own, w, attn, q_in, k_out, by_block, entering, do, dfinal)
    vma = _out_vma(*args)
    *scan, ddecay, dstart = pl.pallas_call(
        body,
        in_specs=[specs[name] for name in _REC_SPECS + (
            "entering", "tokens", "state")],
        out_specs=[specs[name] for name in _REC_SPECS + ("state",)],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in (u_own, w, attn, q_in, k_out, by_block, dfinal)],
        **call)(*args)
    return (*scan, ddecay.swapaxes(1, 2).reshape(decay.shape), dstart)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _recurrence(keep_entering, u_own, w, attn, q_in, k_out, decay, start):
    """The recurrence over chunks through the kernels: ``(o, final)``, see
    :func:`_rec_fwd_call`. ``keep_entering`` (static) names each chunk's
    entering state for a checkpoint around the caller to keep."""
    return tuple(_rec_fwd_call(u_own, w, attn, q_in, k_out, decay, start,
                               keep=False))


def _recurrence_fwd(keep_entering, *inputs):
    # The residuals: the inputs but the initial state, and the entering
    # states the forward kernel keeps, under the names a ``jax.checkpoint``
    # around the caller may keep them by (``SAVED_NAMES``; outside one a
    # name is an identity). With the entering states named
    # the checkpoint has this rule's every output, and the kernel reads the
    # operands unnamed: ``jax.checkpoint`` copies a kept value that the
    # forward pass reads too through a ``reduce_precision``, behind a kernel
    # a pass over every kept byte. Without, the recomputed copy makes the
    # states again and must find the operands kept: the kernel reads them
    # named (the module docstring; PERF.md, Findings, PR 56).
    scan = tuple(checkpoint_name(t, "gdn_scan_operands") for t in inputs[:5])
    o, final, entering = _rec_fwd_call(
        *(inputs if keep_entering else scan + inputs[5:]), keep=True)
    if keep_entering:
        entering = checkpoint_name(entering, "gdn_scan_entering")
    return (o, final), scan + (inputs[5], entering)


def _recurrence_bwd(keep_entering, kept, cotangents):
    return _rec_bwd_call(*kept, *cotangents)


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64, *,
                        dtype: Any = jnp.bfloat16, initial_state=None,
                        beta_max: int = 1, norm_qk: bool = False):
    """The recurrence in chunks of ``chunk`` tokens (a power of two).
    Arguments as :func:`gated_delta_sequential`, key and value heads of any
    size; ``dtype`` is the MXU operands' type. Under ``norm_qk`` (static)
    the chunk-local kernels norm the keys: ``q`` and ``k`` come as the
    caller has them, in ``dtype``, each row of a head is L2-normalised in
    VMEM in float32 (``eps`` 1e-6 under the root), ``q`` times ``K^-0.5``
    for the head's true ``K`` besides, rounded to ``dtype`` once before the
    products, and the gradient comes back for the raw ``q`` and ``k``
    (:func:`unit_rows` is the plain line). Returns ``(o, state)``, ``o``
    ``[B, S, Hv, V]`` in ``dtype`` and the float32 state after the last
    token ``[B, Hv, K, V]``. A length the chunk does not divide is padded
    with tokens that neither decay (``g = 0``) nor write (``beta = 0``); a
    head whose size is no multiple of the lane width is carried with zeros
    to the next one inside (the module docstring says why that is exact).
    ``beta_max`` is what the caller's ``beta`` lies under (1: a sigmoid; 2:
    twice one); it labels the layer's count behind ``hvd.metrics()`` and the
    arithmetic does not read it."""
    _check(q, k, v, g, beta)
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"gated delta rule: chunk must be a power of two, "
                         f"got {chunk}")
    batch, seq, key_heads, key_dim = k.shape
    heads, width = v.shape[2:]
    f32 = jnp.float32
    pad = (-seq) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n_chunks = (seq + pad) // chunk
    runtime.note_traced(
        "hvdtpu_spmd_gdn_layer_traces_total", key_heads=key_heads,
        value_heads=heads, key_dim=key_dim, value_dim=width, chunk=chunk,
        recurrence="kernel", chunks=n_chunks, beta_max=beta_max,
        qk_norm="kernel" if norm_qk else "caller")

    def chunked(t):
        """``[B, S, H]`` -> ``[B, c, Q, H]``."""
        return t.astype(f32).reshape(batch, n_chunks, chunk, heads)

    cum = jnp.cumsum(chunked(g), axis=2)                    # log G_t
    # The kernels: the rows' norms (``norm_qk``), K K^T, Q K^T, the decays,
    # A, T and T's two products stay in VMEM; out come the recurrence's
    # operands, a chunk a turn. From here to ``o`` a head is whole lane
    # tiles (zeros add nothing to a row's sum of squares: the scale is the
    # true head's).
    u_own, w, attn, q_in, k_out = _chunk_local(
        key_dim ** -0.5 if norm_qk else None, to_lanes(q.astype(dtype)),
        to_lanes(k.astype(dtype)),
        to_lanes(v.astype(dtype)), cum, chunked(beta))

    start = jnp.zeros((batch, heads, key_dim, width), f32) \
        if initial_state is None else initial_state.astype(f32)
    # The recurrence's kernels: a head's state stays in VMEM through the
    # sequence's chunks; o comes out as [B, S, Hv V]. Its rule names the
    # five operands for a checkpoint to keep, and the entering states
    # where they are worth the memory: the carried head is the true head.
    o, final = _recurrence(key_dim % LANES == 0 and width % LANES == 0,
                           u_own, w, attn, q_in, k_out,
                           jnp.exp(cum[:, :, -1]),
                           varying_like(to_lanes(start, -2, -1), u_own))
    o = o.reshape(batch, n_chunks * chunk, heads, -1)[:, :seq]
    if o.shape[-1] != width:
        o = o[..., :width]
    if final.shape[2:] != (key_dim, width):
        final = final[:, :, :key_dim, :width]
    return o, final
