"""The gated delta rule: a linear-attention recurrence whose state is
*corrected* by each token, not only added to. Pure ``jax.numpy``.

A value head keeps a state ``S`` in ``R^{K x V}`` (keys by values), zero at
the start of a sequence. With ``alpha_t = exp(g_t)`` in (0, 1] the decay and
``beta_t`` in [0, 1] the writing strength::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(Yang, Kautz and Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464, as
remembered; there is no network here.) The token first reads what the decayed
state already answers for its key, and writes only the difference: with
``beta = 0`` the state only decays, with ``alpha = 1`` it is the ungated
delta rule. Value head ``h`` of ``Hv`` reads key head ``h // (Hv / Hk)``.
``q`` and ``k`` come as the caller made them (the model L2-normalises both
and scales ``q``: ``models/gpt.py::_gdn_mixer``).

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
all chunks at once; a ``lax.scan`` over chunks carries ``S``. Decays, running
sums and ``T`` are float32; the other products take operands in ``dtype``
and accumulate in float32, as ``ops/ssd.py::ssd_chunked`` does.

**Which inverse.** :func:`unit_lower_inverse` inverts ``I + A`` by blocks:
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

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .ssd import _varying_like

_HI = lax.Precision.HIGHEST


def _check(q, k, v, g, beta):
    if q.shape != k.shape or v.shape[2] % k.shape[2] \
            or g.shape != v.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            "gated delta rule: q and k [B, S, Hk, K], v [B, S, Hv, V] with "
            "Hk dividing Hv, g and beta [B, S, Hv]; got "
            f"{q.shape}, {k.shape}, {v.shape}, {g.shape}, {beta.shape}")


def gated_delta_sequential(q, k, v, g, beta, initial_state=None):
    """The recurrence one token a step, float32. ``q``, ``k`` ``[B, S, Hk,
    K]``; ``v`` ``[B, S, Hv, V]``; ``g`` (log decay, <= 0) and ``beta``
    ``[B, S, Hv]``. Returns ``(o [B, S, Hv, V], state [B, Hv, K, V])``."""
    _check(q, k, v, g, beta)
    f32 = jnp.float32
    rep = v.shape[2] // k.shape[2]
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
    and zero elsewhere). ``D_1 = I``, so the first round is a mask."""
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
    in ``a``'s type: the module docstring's inverse by blocks."""
    return _inverse(a)


def _inverse_fwd(a):
    inv = _inverse(a)
    return inv, inv


def _inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.einsum("...ij,...jk,...kl->...il", t, g, t,
                        precision=_HI),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64, *,
                        dtype: Any = jnp.bfloat16, initial_state=None):
    """The recurrence in chunks of ``chunk`` tokens (a power of two).
    Arguments as :func:`gated_delta_sequential`; ``dtype`` is the MXU
    operands' type. Returns ``(o, state)``, ``o`` ``[B, S, Hv, V]`` in
    ``dtype`` and the float32 state after the last token ``[B, Hv, K, V]``.
    A length the chunk does not divide is padded with tokens that neither
    decay (``g = 0``) nor write (``beta = 0``)."""
    _check(q, k, v, g, beta)
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"gated delta rule: chunk must be a power of two, "
                         f"got {chunk}")
    batch, seq, key_heads, key_dim = k.shape
    heads, width = v.shape[2:]
    rep = heads // key_heads
    from .. import runtime
    recorder = runtime.recorder()
    if recorder is not None:
        recorder.note_traced(
            "hvdtpu_spmd_gdn_layer_traces_total", key_heads=key_heads,
            value_heads=heads, key_dim=key_dim, value_dim=width, chunk=chunk)

    f32 = jnp.float32
    pad = (-seq) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n_chunks = (seq + pad) // chunk

    def chunked(t):
        """``[B, S, H, ...]`` -> ``[B, c, H, Q, ...]``."""
        t = t.reshape((batch, n_chunks, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 2, 3)

    def by_value_head(t):
        return jnp.repeat(t, rep, axis=2) if rep > 1 else t

    q, k, v = (chunked(t.astype(dtype)) for t in (q, k, v))
    beta = chunked(beta.astype(f32))                        # [B, c, H, Q]
    cum = jnp.cumsum(chunked(g.astype(f32)), axis=-1)       # log G_t
    last = cum[..., -1:]
    # G_t / G_j for j <= t; the exponent is masked, not the exponential, so
    # nothing above the diagonal overflows.
    below = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(below, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # [B, c, H, Q, Q]
    kk = jnp.einsum("bchik,bchjk->bchij", k, k, preferred_element_type=f32)
    qk = jnp.einsum("bchik,bchjk->bchij", q, k, preferred_element_type=f32)
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strictly,
                  by_value_head(kk) * decay * beta[..., None], 0.0)
    t_inv = unit_lower_inverse(a).astype(dtype)
    attn = (by_value_head(qk) * decay).astype(dtype)
    kv = by_value_head(k).astype(f32)                       # [B, c, H, Q, K]
    qv = by_value_head(q).astype(f32)
    # T (beta V), T (beta G K): what needs no state.
    u_own = jnp.einsum("bchij,bchjv->bchiv", t_inv,
                       (v.astype(f32) * beta[..., None]).astype(dtype),
                       preferred_element_type=f32)
    w = jnp.einsum("bchij,bchjk->bchik", t_inv,
                   (kv * (beta * jnp.exp(cum))[..., None]).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    q_in = (qv * jnp.exp(cum)[..., None]).astype(dtype)
    k_out = (kv * jnp.exp(last - cum)[..., None]).astype(dtype)

    def one_chunk(state, now):
        u_c, w_c, q_c, k_c, attn_c, decay_c = now
        s = state.astype(dtype)
        u = (u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, s,
                              preferred_element_type=f32)).astype(dtype)
        o = jnp.einsum("bhik,bhkv->bhiv", q_c, s,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", attn_c, u,
                         preferred_element_type=f32)
        state = decay_c[..., None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, u, preferred_element_type=f32)
        return state, o.astype(dtype)

    start = jnp.zeros((batch, heads, key_dim, width), f32) \
        if initial_state is None else initial_state.astype(f32)
    final, o = lax.scan(
        one_chunk, _varying_like(start, u_own),
        tuple(jnp.moveaxis(t, 1, 0) for t in
              (u_own, w, q_in, k_out, attn, jnp.exp(last))))
    # [c, B, H, Q, V] -> [B, S, H, V]
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3)
    return o.reshape(batch, n_chunks * chunk, heads, width)[:, :seq], final
