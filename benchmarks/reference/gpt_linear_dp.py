"""Plain float32 reference of the ``gpt_linear_dp`` job: a dense decoder whose
layers are gated-delta-rule linear attention or full softmax attention, each
followed by a SiLU-gated feed-forward, every branch normed *after* it
(``model_type: olmo_hybrid``), its loss, gradient and AdamW first step.

``jax.numpy`` alone, every product at ``highest`` precision (the caller sets
``jax.default_matmul_precision("highest")`` too), no kernel, no chunking, no
padding: the linear-attention recurrence is a ``lax.scan`` over time, one
token a step, at the published head sizes. Written from the published
configuration's keys; what they do not say is the configuration file's
``assumed`` (the Olmo 2 / Olmo 3 block, the Gated DeltaNet layer as
``gpt_linear_moe_dp.py`` has it for the configuration whose ``linear_*`` key
names this one shares); there is no network here. The equations, their one
written home::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w                 # RMSNorm, plain
    per layer:  h = x + N(mixer(x));  y = h + N(ff(h))      # no norm before
    ff(h) = W_down (silu(W_gate h) * W_up h)                # a branch
    logits = W_head N(x_L)                                  # untied head
    loss = mean over the rows held of the next token's cross-entropy

    full-attention mixer (H heads of D, as many key/value heads):
        q, k, v = W_q x, W_k x, W_v x
        q = N(q; w_q), k = N(k; w_k) over the whole projection (all H D
            channels of a token together), no position embedding
        out = W_o softmax_causal(q k^T / sqrt(D)) v

    linear-attention mixer (H heads: value head h reads key head h; keys of
    K, values of V):
        [q | k | v | z] = W_qkvz x        (H K | H K | H V | H V)
        [b | a] = W_ba x                  (H | H)
        [q | k | v] = silu(conv([q | k | v])): causal, depthwise, 4 taps, no
            bias, conv(u)_t = sum_j w_j u_{t-3+j}, zeros before the start
        q = q / sqrt(|q|^2 + 1e-6) / sqrt(K);  k = k / sqrt(|k|^2 + 1e-6)
        beta = 2 sigmoid(b)               # linear_allow_neg_eigval
            (sigmoid(b) without it: ``beta_max`` is 2 or 1)
        alpha = exp(-exp(A_log) softplus(a + dt_bias))
        state S in R^{K x V} a head, S_0 = 0 at each sequence's start:
            S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
            o_t = S_t^T q_t
        y = (o / sqrt(mean(o^2) + eps) * w_norm) * silu(z)  # a value head
        out = W_out y

With ``beta`` in (0, 2) a token's transition ``alpha (I - beta k k^T)`` has
its eigenvalue along the key in (-1, 1): the state can flip sign along a key,
not only shrink.

Departures from "plain": each layer is wrapped in ``jax.checkpoint``, and so
is each run of ``SCAN_BLOCK`` tokens of the time scan, so that its per-step
states (2.2 MB a token a layer at the published sizes, several of them a
step) are held for one run of one layer at a time: with them all kept the
gradient of a 1024-token sequence takes 18 GiB beside the parameters and no
chip holds it; the arithmetic is unchanged, one token a step in order. The
published ``in_proj_qkvz`` may interleave its columns by head; the tree
holds them as ``[q | k | v | z]``, a permutation of columns.

It reads the parameter tree ``models/gpt.py::init_params`` makes (a layer
with a ``gdn`` entry is a linear-attention layer; the norms after the
branches are ``mixer_post_norm`` and ``mlp_post_norm``); parameters are the
interface, the arithmetic is its own. It imports nothing from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_dp import adamw_first_update_norm  # noqa: F401

HI = lax.Precision.HIGHEST
SCAN_BLOCK = 64     # tokens whose per-step states the backward pass holds


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention_mixer(x, p, eps):
    batch, seq = x.shape[:2]
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"], precision=HI)
    k = jnp.einsum("bse,ehd->bshd", x, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", x, p["wv"], precision=HI)

    def whole(t, w):
        """The norm over a token's whole projection, all heads together."""
        flat = _rmsnorm(t.reshape(batch, seq, -1), w.reshape(-1), eps)
        return flat.reshape(t.shape)

    q, k = whole(q, p["q_norm"]), whole(k, p["k_norm"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI)


def recurrence(q, k, v, alpha, beta):
    """The gated delta rule itself, one token a step. ``q``, ``k`` ``[b, S,
    H, K]``, ``v`` ``[b, S, H, V]``, ``alpha`` and ``beta`` ``[b, S, H]`` ->
    ``o`` ``[b, S, H, V]``."""

    def step(state, now):
        q_t, k_t, v_t, a_t, b_t = now        # [b,H,K] [b,H,K] [b,H,V] [b,H]
        state = a_t[..., None, None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   b_t[..., None] * (v_t - seen),
                                   precision=HI)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    @jax.checkpoint
    def run(state, tokens):
        return lax.scan(step, state, tokens)

    seq = q.shape[1]
    block = math.gcd(seq, SCAN_BLOCK)
    start = jnp.zeros(v.shape[:1] + v.shape[2:3] + k.shape[3:] + v.shape[3:],
                      v.dtype)
    # [b, S, ...] -> [S / block, block, b, ...]: the runs in order.
    _, o = lax.scan(run, start, tuple(
        jnp.moveaxis(t, 1, 0).reshape((seq // block, block, t.shape[0])
                                      + t.shape[2:])
        for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o.reshape((seq,) + o.shape[2:]), 0, 1)


def _linear_mixer(x, p, *, key_dim, beta_max, eps):
    heads = p["A_log"].shape[0]             # one value head a key head
    width = p["norm"].shape[0]
    key_inner = heads * key_dim
    conv_dim = p["conv_w"].shape[1]
    if conv_dim != 2 * key_inner + heads * width:
        raise ValueError("the reference runs one value head a key head: "
                         f"{conv_dim} convolved channels are not 2 x {heads} "
                         f"x {key_dim} + {heads} x {width}")
    batch, seq = x.shape[:2]
    qkvz = jnp.einsum("bse,ef->bsf", x, p["in_proj"], precision=HI)
    qkv, z = jnp.split(qkvz, [conv_dim], axis=-1)
    b, a = jnp.split(jnp.einsum("bse,ef->bsf", x, p["in_proj_ba"],
                                precision=HI), 2, axis=-1)
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + seq] * p["conv_w"][j]
                          for j in range(taps)))
    q, k, v = jnp.split(qkv, [key_inner, 2 * key_inner], axis=-1)

    def unit(t):
        t = t.reshape(batch, seq, heads, key_dim)
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    o = recurrence(unit(q) / math.sqrt(key_dim), unit(k),
                   v.reshape(batch, seq, heads, width), alpha,
                   beta_max * jax.nn.sigmoid(b))
    y = _rmsnorm(o, p["norm"], eps) * jax.nn.silu(z.reshape(o.shape))
    return jnp.einsum("bsf,fe->bse", y.reshape(batch, seq, heads * width),
                      p["out_proj"], precision=HI)


def _layer(x, p, *, key_dim, beta_max, norm_eps):
    if "gdn" in p:
        mixed = _linear_mixer(x, p["gdn"], key_dim=key_dim,
                              beta_max=beta_max, eps=norm_eps)
    else:
        mixed = _attention_mixer(x, p, norm_eps)
    h = x + _rmsnorm(mixed, p["mixer_post_norm"], norm_eps)
    gate = jnp.einsum("bse,em->bsm", h, p["w_gate"], precision=HI)
    up = jnp.einsum("bse,em->bsm", h, p["w_up"], precision=HI)
    ff = jnp.einsum("bsm,me->bse", jax.nn.silu(gate) * up, p["w_down"],
                    precision=HI)
    return h + _rmsnorm(ff, p["mlp_post_norm"], norm_eps)


def shard_loss(params, tokens, targets, *, norm_eps: float, **layer):
    """Mean next-token cross-entropy over the targets that are not -1.
    ``layer`` holds ``key_dim`` (the linear layers' key head size, which the
    tree's shapes alone do not tell from the number of heads) and
    ``beta_max`` (2 under ``linear_allow_neg_eigval``, else 1)."""
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = jax.checkpoint(
            lambda x, p: _layer(x, p, norm_eps=norm_eps, **layer))(x, p)
    x = _rmsnorm(x, params["out_norm"], norm_eps)
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ev->bsv", x, params["lm_head"], precision=HI))
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
