"""Plain float32 reference of the ``gpt_prerouted_moe_dp`` job: a decoder
whose every block routes its experts **from the block's own input, before
attention**, through ReLU-gated experts, under one full layer without a
position embedding and three sliding-window layers with the rotary one a
period (``smallthinker``: SmallThinker-21BA3B-Instruct), its loss, gradient
and AdamW first step.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
sort and no grouped matmul: ``S x S`` logits under the band or the causal
mask, a block of query rows at a time; every held expert applied to every
token under a 0/1 mask of the choice. Written from the published
configuration's keys and from what the catalog says of the family ("router
placed before attention", "sparse ReGLU", "SWA(4096); NoPE global"); what is
no key of the config is listed under ``assumed`` in the configuration file,
(a) below. With ``x`` the stream entering block ``l``::

    RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w                # plain weight
    x_0 = E[tokens]
    r   = x W_r                      float32; the router reads the block's
                                     OWN INPUT, un-normed, before attention (a)
    S_t = the k largest of r_t       w_t = softmax(r_t restricted to S_t)
                                     (softmax over all E, the k kept, divided
                                     by their sum: the same numbers)
    a   = x + Attn_l(N1(x)) W_o      H query heads over Hkv key/value heads of
                                     D, no bias, no q/k norm, logits over
                                     sqrt(D), key/value heads repeated
        rope_layout[l] == 0:           no position embedding
        rope_layout[l] == 1:           rotate-half rotary at base theta on
                                       all D dimensions (a)
        sliding_window_layout[l] == 0: the causal mask
        sliding_window_layout[l] == 1: 0 <= i - j < W (a query sees itself
                                       and the W - 1 keys before it)
    y   = a + sum_{e in S_t, e held} w_te W_down,e(relu(W_gate,e h) * W_up,e h)
          h = N2(a);   relu'(0) = 0
    logits = W_head RMSNorm(x_L)                                 # untied head
    loss: mean next-token cross-entropy over the vocabulary held; no
    auxiliary term (a).

**This chip's share**: the tree holds experts ``first_expert`` to
``first_expert + held`` of E (``held`` is the expert matrices' first axis);
the router, the choice and the softmax over the chosen are over all E, the
sum over the held ones alone, and that partial sum goes on to the next
layer. Nothing stands in for the absent experts.

Departures from "plain", so that the job's whole 16,384-token sequence
fits on the chip beside the parameters and their gradient: each layer is
wrapped in ``jax.checkpoint``; its attention makes the logits of a block of
query rows at a time (``LOGIT_ELEMENTS`` a head: 256 rows at 16,384 keys, 0.47
GB of float32 logits over 28 heads), the blocks a ``lax.map`` whose body is
under a checkpoint of its own; its experts are applied one at a time, each
with its weight under a checkpoint; the head's logits are made ``HEAD_ROWS``
rows at a time. The arithmetic is unchanged.

It reads the parameter tree ``models/gpt.py::init_params`` makes, and each
layer's kind from the published layouts handed in by the job; parameters are
the interface, the arithmetic is its own. It imports nothing from the
program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_dp import adamw_first_update_norm  # noqa: F401
from benchmarks.reference.gpt_window_moe_dp import router_logits  # noqa: F401

HI = lax.Precision.HIGHEST
# A block of query rows' logits a head, in elements: rows x keys.
LOGIT_ELEMENTS = 1 << 22
HEAD_ROWS = 512


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half on all of the last axis; ``x`` ``[b, S, heads, D]``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _rows_attention(q, k, v, first, window):
    """Query rows ``first ..`` (a scalar array) of every head against all
    keys under the causal mask or the band: ``q`` ``[b, R, H, D]``, ``k`` and
    ``v`` ``[b, S, H, D]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    i = first + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    keep = i >= j
    if window is not None:
        keep = keep & (i - j < window)
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=HI)


def attention(h, p, positions, *, window, rope: bool, rope_theta: float):
    """``window`` None: every key before the query; ``rope``: whether the
    layer has the rotary embedding."""
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    if rope:
        q = _rope(q, positions, rope_theta)
        k = _rope(k, positions, rope_theta)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    b, seq = q.shape[:2]
    rows = min(seq, max(1, LOGIT_ELEMENTS // seq))
    if seq % rows:
        raise ValueError(f"{seq} keys are no whole number of blocks of "
                         f"{rows} query rows")
    block = jax.checkpoint(
        lambda rows_q, first: _rows_attention(rows_q, k, v, first, window))
    a = lax.map(lambda xs: block(*xs),
                (jnp.moveaxis(q.reshape(b, seq // rows, rows, *q.shape[2:]),
                              1, 0), jnp.arange(0, seq, rows)))
    a = jnp.moveaxis(a, 0, 1).reshape(q.shape)
    return jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI)


def routing(r, top_k: int):
    """``(weights [T, E], chosen [T, E])`` from a router's outputs ``r``:
    ``chosen`` is 1 on a token's ``top_k`` largest and 0 elsewhere (ties to
    the lower index), ``weights`` the softmax of ``r`` over the chosen and 0
    elsewhere."""
    _, top_e = lax.top_k(r, top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, r.shape[-1], dtype=r.dtype),
                     axis=1)
    return jax.nn.softmax(jnp.where(chosen > 0, r, -jnp.inf), axis=-1), chosen


def _expert(h, weight, w_gate, w_up, w_down):
    """One expert on every token under the tokens' weights for it ``[T]``
    (0 where it was not chosen)."""
    gate = jnp.dot(h, w_gate, precision=HI)
    # relu with relu'(0) = 0 (jnp.maximum's derivative at 0 is a half).
    hidden = jnp.where(gate > 0, gate, 0.0) * jnp.dot(h, w_up, precision=HI)
    return weight[:, None] * jnp.dot(hidden, w_down, precision=HI)


def expert_block(h, r, m, top_k: int, first_expert: int = 0):
    """``h`` ``[T, d]`` what the experts read, ``r`` ``[T, E]`` the router's
    outputs, ``m`` the block's parameters -> ``(y [T, d], tokens per expert
    [E])``; ``y`` is the held experts' part of the sum."""
    weights, chosen = routing(r, top_k)
    y = jnp.zeros_like(h)
    for e in range(m["w_up"].shape[0]):
        y = y + jax.checkpoint(_expert)(
            h, weights[:, first_expert + e], m["w_gate"][e], m["w_up"][e],
            m["w_down"][e])
    return y, jnp.sum(chosen, axis=0)


def _layer(x, p, positions, *, window, rope, top_k, first_expert, rope_theta,
           norm_eps):
    r = router_logits(x.reshape(-1, x.shape[-1]), p["moe"]["router"])
    x = x + attention(_rmsnorm(x, p["attn_norm"], norm_eps), p, positions,
                      window=window, rope=rope, rope_theta=rope_theta)
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    y, counts = expert_block(h.reshape(-1, h.shape[-1]), r, p["moe"], top_k,
                             first_expert)
    return x + y.reshape(x.shape), counts


def _rows_loss(x, targets, head):
    """Summed cross-entropy of rows ``x`` ``[R, d]`` against ``targets``
    ``[R]`` (-1: none)."""
    logp = jax.nn.log_softmax(jnp.dot(x, head, precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0))


def shard_loss(params, tokens, targets, positions, *, windows, ropes,
               norm_eps: float, **layer):
    """``(loss, parts)``: ``parts`` holds ``counts`` ``[layers, E]``.
    ``windows`` has each layer's window (None: every key before the query),
    ``ropes`` whether it has the rotary embedding; ``layer`` holds ``top_k``,
    ``first_expert`` and ``rope_theta``."""
    x = params["embed"][tokens]
    counts = []
    for p, window, rope in zip(params["layers"], windows, ropes,
                               strict=True):
        x, c = jax.checkpoint(
            lambda x, p, window=window, rope=rope: _layer(
                x, p, positions, window=window, rope=rope,
                norm_eps=norm_eps, **layer))(x, p)
        counts.append(c)
    x = _rmsnorm(x, params["out_norm"], norm_eps).reshape(-1, x.shape[-1])
    flat = targets.reshape(-1)
    total = sum(
        jax.checkpoint(_rows_loss)(x[i:i + HEAD_ROWS],
                                   flat[i:i + HEAD_ROWS], params["lm_head"])
        for i in range(0, x.shape[0], HEAD_ROWS))
    return total / jnp.sum(flat != -1), {"counts": jnp.stack(counts)}


def loss_and_grad(params, tokens, targets, positions, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss, tokens per expert summed over the shards ``[layers, E]``,
    and the mean gradient."""
    fn = jax.jit(lambda p, *data: jax.value_and_grad(
        lambda q: shard_loss(q, *data, **model), has_aux=True)(p))
    counts = []

    def one(p, *data):
        (loss, aux), grad = fn(p, *data)
        counts.append(jax.device_get(aux["counts"]))
        return loss, grad

    loss, grad = shards.loss_and_grad(one, params, tokens, targets, positions)
    return loss, sum(counts), grad
