"""Plain float32 reference of the ``gpt_hybrid_dp`` job: a decoder whose
layers differ in kind (Mamba-2 state-space mixers beside grouped-query
attention), its loss, gradient and AdamW first step.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
chunking: the state-space recurrence is a ``lax.scan`` over time, one token a
step. Written from the published configuration's keys
(``model_type: granitemoehybrid``) and the Mamba-2 paper (Dao and Gu,
arXiv:2405.21060, section 7 and listing 1) as remembered; there is no
network here. The equations, ``m_*`` the model's four scalars::

    x0 = m_embed * embed[tokens]
    per layer:  h = RMSNorm(x);  x = x + m_res * mixer(h)
                h = RMSNorm(x);  x = x + m_res * W_down (silu(W_gate h) * W_up h)
    logits = (embed^T RMSNorm(x_L)) / m_logits              # tied head

    attention mixer: q, k, v = W_q h, W_k h, W_v h (no bias, no rotary
        embedding, no q/k norm; key and value heads repeated explicitly);
        causal softmax(q k^T * m_attn) v, then W_o
    state-space mixer: [z | xBC | dt] = W_in h  (inner | inner + 2 G N | H)
        xBC = silu(conv(xBC)): causal, depthwise, K taps and a bias,
            conv(u)_t = b + sum_k w_k u_{t-(K-1)+k}, zeros before the start
        [x | B | C] = split(xBC)                            # inner | G N | G N
        dt = softplus(dt + dt_bias);  A = -exp(A_log)       # one a head
        head h of group g (x_t in R^P), state S in R^{P x N}, S_0 = 0:
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        y = RMSNorm(y * silu(z)) * w  (over the whole inner width)
        out = W_out y

Departures from "plain": each layer is wrapped in ``jax.checkpoint`` so that
the time scan's per-step states (2 MB a token a layer at the published
widths) are held for one layer at a time; the arithmetic is unchanged. The
published ``input_linear`` of the feed-forward is one matrix whose halves
are the gate and the up projection; the parameter tree holds the halves.

It reads the parameter tree ``models/gpt.py::init_params`` makes (a layer
with an ``ssm`` entry is a state-space layer); parameters are the interface,
the arithmetic is its own. It imports nothing from the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_dp import adamw_first_update_norm  # noqa: F401

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention_mixer(h, p, m_attn):
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * m_attn
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI)


def recurrence(x, dt, a, b_in, c_in, d):
    """The state-space recurrence itself, one token a step. ``x`` ``[b, S,
    H, P]``, ``dt`` ``[b, S, H]``, ``a`` and ``d`` ``[H]``, ``b_in`` and
    ``c_in`` ``[b, S, G, N]`` (head ``h`` reads group ``h // (H / G)``) ->
    ``y`` ``[b, S, H, P]``."""
    heads, groups = x.shape[2], b_in.shape[2]
    b_in = jnp.repeat(b_in, heads // groups, axis=2)        # [b, S, H, N]
    c_in = jnp.repeat(c_in, heads // groups, axis=2)

    def step(state, now):
        x_t, dt_t, b_t, c_t = now           # [b,H,P] [b,H] [b,H,N] [b,H,N]
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] \
            * b_t[..., None, :]
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HI)
        return state, y_t + d[:, None] * x_t

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b_in.shape[-1:], x.dtype)
    _, y = lax.scan(step, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b_in, c_in)))
    return jnp.moveaxis(y, 0, 1)


def _ssm_mixer(h, p, state, eps):
    heads = p["A_log"].shape[0]
    inner = p["norm"].shape[0]
    conv_dim = p["conv_b"].shape[0]
    groups = (conv_dim - inner) // (2 * state)
    batch, seq = h.shape[:2]
    zxbcdt = jnp.einsum("bse,ef->bsf", h, p["in_proj"], precision=HI)
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + seq] * p["conv_w"][k] for k in range(taps)))
    x, b_in, c_in = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    y = recurrence(
        x.reshape(batch, seq, heads, inner // heads),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b_in.reshape(batch, seq, groups, state),
        c_in.reshape(batch, seq, groups, state), p["D"])
    y = _rmsnorm(y.reshape(batch, seq, inner) * jax.nn.silu(z), p["norm"],
                 eps)
    return jnp.einsum("bsf,fe->bse", y, p["out_proj"], precision=HI)


def _layer(x, p, *, attention_multiplier, residual_multiplier, ssm_state,
           norm_eps):
    if "ssm" in p:
        h = _rmsnorm(x, p["ssm_norm"], norm_eps)
        mixed = _ssm_mixer(h, p["ssm"], ssm_state, norm_eps)
    else:
        h = _rmsnorm(x, p["attn_norm"], norm_eps)
        mixed = _attention_mixer(h, p, attention_multiplier)
    x = x + residual_multiplier * mixed
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    gate = jnp.einsum("bse,em->bsm", h, p["w_gate"], precision=HI)
    up = jnp.einsum("bse,em->bsm", h, p["w_up"], precision=HI)
    return x + residual_multiplier * jnp.einsum(
        "bsm,me->bse", jax.nn.silu(gate) * up, p["w_down"], precision=HI)


def shard_loss(params, tokens, targets, *, embedding_multiplier: float,
               logits_scaling: float, norm_eps: float, **layer):
    """Mean next-token cross-entropy over the targets that are not -1.
    ``layer`` holds ``attention_multiplier``, ``residual_multiplier`` and
    ``ssm_state`` (the state's width N, which the tree's shapes alone do not
    tell from the number of groups)."""
    x = embedding_multiplier * params["embed"][tokens]
    for p in params["layers"]:
        x = jax.checkpoint(
            lambda x, p: _layer(x, p, norm_eps=norm_eps, **layer))(x, p)
    x = _rmsnorm(x, params["out_norm"], norm_eps)
    logp = jax.nn.log_softmax(jnp.einsum(
        "bse,ve->bsv", x, params["embed"], precision=HI) / logits_scaling)
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)


def loss_and_grad(params, tokens, targets, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss and the mean gradient."""
    fn = jax.jit(jax.value_and_grad(
        lambda p, *data: shard_loss(p, *data, **model)))
    return shards.loss_and_grad(fn, params, tokens, targets)
