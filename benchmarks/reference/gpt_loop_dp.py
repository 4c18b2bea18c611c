"""Plain float32 reference of the ``gpt_loop_dp`` job: a **looped** decoder's
loss, its parts, its gradient and its first update (Ouro, ``model_type:
ouro``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

``jax.numpy`` alone, every product at ``highest`` precision, no kernel,
independent of ``horovod_tpu/models`` and ``horovod_tpu/ops``. **Its parts
are compiled, the whole is not**: a block is one program called T x L times
a sequence and a pass's log-softmax one called T times (each keeps its
input alone, ``jax.checkpoint``, so the ``S x S`` scores and the logits of
all those calls are never held at once), and ``jax.grad`` walks the Python
loops over them (:func:`loss_and_grad`): what compiles is a block, not
T x L of them.
With ``E`` the width, ``T`` the passes and ``L`` the layers kept:

*A block* (sandwich norms; every ``N`` an RMSNorm with its own weight of
``E``, the weight as it is)::

    a = x + N2(Attn(N1(x)))          y = a + N4(MLP(N3(a)))

``Attn(n)``: ``q = W_q n``, ``k = W_k n``, ``v = W_v n`` in heads, no bias;
the rotary embedding on the whole head (two halves against each other) on q
and k; scores ``q . k / sqrt(head)`` under the causal mask, all ``S x S`` of
them, softmax, the values, ``W_o``. ``MLP(n) = W_down (silu(W_gate n) *
W_up n)``.

*The loop*: ``h^0 = Embed(tokens)``; for t = 1..T: ``u = h^{t-1}``, ``u =
Block_l(u)`` for l = 1..L **on the same parameter dictionaries in every
pass**, ``h^t = N_out(u)``, the one final norm, at the end of every pass:
what the head and the gate read at pass t and what pass t + 1 starts from.
Logits ``z^t = W_head h^t``, ``T`` full log-softmaxes.

*The gate and the exit distribution*: ``lambda^t = sigmoid(w_g . h^t +
b_g)``; ``p^1 = lambda^1``, ``p^t = lambda^t prod_{j<t} (1 - lambda^j)``,
``p^T = prod_{j<T} (1 - lambda^j)`` (``lambda^T`` is not read).

*The loss*, with ``l^t_i = -log softmax(z^t_i)[x_{i+1}]`` and N the targets
that are not -1::

    loss = 1/N sum_i [ sum_t p^t_i l^t_i - beta H(p_i) ],
    H(p_i) = -sum_t p^t_i log p^t_i

and no stop-gradient anywhere. It reads the parameter tree
``models/gpt.py::init_params`` makes; parameters are the interface, the
arithmetic is its own.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_dp import adamw_first_update_norm  # noqa: F401

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, base):
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


@functools.partial(jax.jit, static_argnums=(3, 4))
@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def block(x, p, positions, rope_theta, norm_eps):
    """One sandwich-normed block on the parameter dictionary ``p``: one
    program, whatever the pass and the layer."""
    n = _rmsnorm(x, p["attn_norm"], norm_eps)
    q = _rope(jnp.einsum("bse,ehd->bshd", n, p["wq"], precision=HI),
              positions, rope_theta)
    k = _rope(jnp.einsum("bse,ehd->bshd", n, p["wk"], precision=HI),
              positions, rope_theta)
    v = jnp.einsum("bse,ehd->bshd", n, p["wv"], precision=HI)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    rows = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((rows, rows), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    a = x + _rmsnorm(jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI),
                     p["mixer_post_norm"], norm_eps)
    n = _rmsnorm(a, p["mlp_norm"], norm_eps)
    gate = jnp.einsum("bse,em->bsm", n, p["w_gate"], precision=HI)
    up = jnp.einsum("bse,em->bsm", n, p["w_up"], precision=HI)
    down = jnp.einsum("bsm,me->bse", jax.nn.silu(gate) * up, p["w_down"],
                      precision=HI)
    return a + _rmsnorm(down, p["mlp_post_norm"], norm_eps)


_out_norm = jax.jit(_rmsnorm, static_argnums=2)


def pass_states(params, tokens, positions, passes, rope_theta, norm_eps):
    """``[h^1 .. h^T]``: the loop written as T x L block calls."""
    h, states = params["embed"][tokens], []
    for _ in range(passes):
        for p in params["layers"]:
            h = block(h, p, positions, rope_theta, norm_eps)
        h = _out_norm(h, params["out_norm"], norm_eps)
        states.append(h)
    return states


@jax.jit
@jax.checkpoint
def cross_entropies(h, head, targets):
    """A pass's ``l_i`` ``[b, S]`` from its state, zero where the target is
    -1: a whole log-softmax over the vocabulary, one program whatever the
    pass, its logits made again for the gradient and not kept."""
    keep = targets != -1
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ev->bsv", h, head, precision=HI))
    return jnp.where(keep, -jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0], 0.0)


def exit_distribution(gate, states):
    """``p [T, b, S]`` from the passes' states: the products written out."""
    lam = [jax.nn.sigmoid(jnp.einsum("bse,e->bs", h, gate["w"], precision=HI)
                          + gate["b"]) for h in states]
    left, p = jnp.ones_like(lam[0]), []
    for lam_t in lam[:-1]:
        p.append(lam_t * left)
        left = left * (1.0 - lam_t)
    return jnp.stack(p + [left])


@functools.partial(jax.jit, static_argnames="beta")
def _sums(gate, states, each, keep, beta):
    p = exit_distribution(gate, states)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)

    def kept(x):
        return jnp.sum(jnp.where(keep, x, 0.0), axis=(-2, -1))

    expected = kept(jnp.sum(p * each, axis=0))
    return {"loss": expected - beta * kept(entropy),
            "cross_entropy": expected, "pass_losses": kept(each),
            "exit_probs": kept(p), "exit_entropy": kept(entropy)}


def kept_sums(params, tokens, targets, positions, *, passes, beta,
              rope_theta, norm_eps):
    """Sums over the targets kept of the rows ``[b, S]``: ``"loss"`` (of
    ``sum_t p^t_i l^t_i - beta H(p_i)``), ``"cross_entropy"`` (its first
    term), ``"pass_losses"`` ``[T]`` (of each ``l^t_i``), ``"exit_probs"``
    ``[T]`` and ``"exit_entropy"``."""
    states = pass_states(params, tokens, positions, passes, rope_theta,
                         norm_eps)
    each = jnp.stack([cross_entropies(h, params["lm_head"], targets)
                      for h in states])                     # [T, b, S]
    return _sums(params["exit_gate"], states, each, targets != -1, beta)


def shard_loss(params, tokens, targets, positions, **model):
    """``(loss, parts)`` of one shard ``[b, S]``, a sequence at a time:
    ``parts`` holds the expected cross-entropy, each pass's mean
    cross-entropy ``[T]``, the mean exit distribution ``[T]`` and the mean
    entropy, over the shard's targets kept."""
    sums = [kept_sums(params, *rows, **model)
            for rows in zip(tokens[:, None], targets[:, None],
                            positions[:, None])]            # [1, S] each
    count = jnp.sum(targets != -1)
    parts = {name: sum(s[name] for s in sums) / count for name in sums[0]}
    return parts.pop("loss"), parts


def loss_and_grad(params, tokens, targets, positions, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``kept_sums``'s keywords.
    The mean loss, the mean of each part (``numpy``) and the mean gradient,
    the exit gate's in it under ``"exit_gate"``. **Not compiled as a
    whole**: ``jax.grad`` walks ``shard_loss``'s Python loops over the
    compiled parts, so a shard of any number of sequences compiles no
    program of its own, holds a block's input a call and one gradient, and
    makes the ``S x S`` scores and a pass's logits one call at a time."""
    fn = jax.value_and_grad(lambda *args: shard_loss(*args, **model),
                            has_aux=True)
    parts = []

    def value_and_grad(*args):
        (loss, part), grad = fn(*args)
        parts.append(jax.device_get(part))
        return loss, grad

    loss, grad = shards.loss_and_grad(value_and_grad, params, tokens,
                                      targets, positions)
    return loss, jax.tree.map(lambda *xs: sum(xs) / len(xs), *parts), grad
