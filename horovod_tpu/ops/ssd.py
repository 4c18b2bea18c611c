"""The state-space layer's mathematics (Mamba-2's "state-space duality"; Dao
and Gu, arXiv:2405.21060): the chunked scan, the causal depthwise
convolution in front of it, and the plain recurrence the tests hold the scan
to, as :func:`horovod_tpu.ops.attention.default_attention` is for the flash
kernels. Pure ``jax.numpy``; XLA lowers it.

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
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv1d(u, weight, bias):
    """Causal depthwise convolution along the sequence: ``u`` ``[B, S, C]``,
    ``weight`` ``[K, C]``, ``bias`` ``[C]`` -> float32 ``[B, S, C]`` with
    ``out_t = bias + sum_k weight[k] u_{t - (K - 1) + k}`` and zeros before
    the start (tap ``K - 1`` reads the token itself)."""
    taps, seq = weight.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for k in range(taps):
        out = out + padded[:, k:k + seq] * weight[k].astype(jnp.float32)
    return out


def _by_group(t, groups: int, axis: int):
    """The heads on ``axis`` split into ``[G, H / G]``."""
    return t.reshape(t.shape[:axis] + (groups, t.shape[axis] // groups)
                     + t.shape[axis + 1:])


def _varying_like(x, like):
    """``x`` marked as varying over the mesh axes ``like`` varies over:
    inside a ``shard_map`` a scan's carry must enter with the type it leaves
    with."""
    axes = jax.typeof(like).vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(axes), to="varying") if axes else x


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
        recorder.note_ssm_layer(heads, width, state, groups, chunk)

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
    xc, bc, cc = chunked(x), chunked(b_in.astype(dtype)), \
        chunked(c_in.astype(dtype))
    dtc = chunked(dt)                                       # [B, c, Q, H]
    # Running sums of a_t = dt_t A inside a chunk, heads before tokens.
    cum = jnp.cumsum(dtc * a.astype(f32), axis=2).transpose(0, 1, 3, 2)
    last = cum[..., -1]                                     # [B, c, H]
    xdt = xc.astype(f32) * dtc[..., None]                   # [B, c, Q, H, P]

    # Inside a chunk: (C B^T, masked and weighted by the decay) x.
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                    preferred_element_type=f32)             # [B, c, G, Q, Q]
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    # The mask goes on the exponent: above the diagonal cum_i - cum_j is
    # positive and may overflow, and 0 * inf would reach the gradient.
    decay = jnp.exp(jnp.where(keep, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # [B, c, H, Q, Q]
    weights = (_by_group(decay, groups, 2) * cb[:, :, :, None]).astype(dtype)
    y = jnp.einsum("bcgkij,bcjgkp->bcigkp", weights,
                   _by_group(xdt.astype(dtype), groups, 3),
                   preferred_element_type=f32)

    # A chunk's own state: what its tokens leave at its end.
    to_end = jnp.exp(last[..., None] - cum).transpose(0, 1, 3, 2)
    own = jnp.einsum("bcjgkp,bcjgn->bcgkpn",
                     _by_group((xdt * to_end[..., None]).astype(dtype),
                               groups, 3), bc, preferred_element_type=f32)
    own = own.reshape(batch, n_chunks, heads, width, state)

    # The short recurrence over chunks, float32 and elementwise.
    def carry_on(entering, now):
        own_c, decay_c = now
        return decay_c[..., None, None] * entering + own_c, entering

    start = jnp.zeros((batch, heads, width, state), f32) \
        if initial_state is None else initial_state.astype(f32)
    final, entering = lax.scan(
        carry_on, _varying_like(start, own), (own.swapaxes(0, 1),
                          jnp.exp(last).swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                      # [B, c, H, P, N]

    # What the entering state gives each token through C.
    through = jnp.einsum(
        "bcign,bcgkpn->bcigkp", cc,
        _by_group(entering.astype(dtype), groups, 2),
        preferred_element_type=f32)
    y = y + through * _by_group(jnp.exp(cum).transpose(0, 1, 3, 2),
                                groups, 3)[..., None]
    y = y.reshape(batch, seq + pad, heads, width)[:, :seq]
    y = y + d.astype(f32)[:, None] * x[:, :seq].astype(f32)
    return y.astype(dtype), final


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
