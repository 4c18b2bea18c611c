"""Plain float32 reference of the ``gpt_mla_moe_dp`` job: a decoder whose
attention is latent attention (MLA: keys and values from a normed latent, a
192-wide query/key head beside a 128-wide value head, one rotary key a token
for all heads) over a SiLU-gated dense feed-forward and then expert blocks
with a sigmoid router under a selection bias and an ungated shared expert
(``model_type: deepseek_v3``, Moonlight-16B-A3B), its loss, gradient, AdamW
first step and the bias's update.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
sort and no grouped matmul: ``S x S`` logits under the causal mask, every
held expert applied to every token. Written from the published
configuration's keys; what is no key of it is from the DeepSeek-V2 and V3
reports and ``modeling_deepseek_v3.py`` as remembered (there is no network
here) and is listed under ``assumed`` in the configuration file, (a) below.
The equations, ``H`` heads, ``dn = qk_nope_head_dim``, ``dr =
qk_rope_head_dim``, ``dv = v_head_dim``, ``r = kv_lora_rank``::

    RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w                # plain weight
    per layer:  x = x + MLA(N1(x));  x = x + FF(N2(x))
    logits = W_head RMSNorm(x_L)                                 # untied head

    MLA(h), no bias anywhere, no latent on the query side (q_lora_rank null):
        q_h  = [qn_h (dn) | qr_h (dr)] = h W_q              W_q [E, H, dn + dr]
        [c0 (r) | kr0 (dr)] = h W_kv_a                      W_kv_a [E, r + dr]
        c    = RMSNorm(c0)                                  weight [r]
        [kn_h (dn) | v_h (dv)] = c W_kv_b                   W_kv_b [r, H, dn + dv]
        qr_h <- rope(qr_h), kr <- rope(kr0): rotate-half on all dr dimensions
            at base rope_theta (a); kr is ONE head a token, shared by all H
        q_h = [qn_h | qr_h], k_h = [kn_h | kr]              dn + dr each
        o_h = causal softmax(q_h k_h^T / sqrt(dn + dr)) v_h  (a): no further
            factor on the scale (no rope_scaling key)
        MLA(h) = [o_0 .. o_{H-1}] W_o                       W_o [H, dv, E]

    FF, layers 0 .. first_k_dense_replace - 1:  W_d(silu(W_g h) * W_u h)
    FF, every other layer: E router outputs, k a token, the shared experts as
    one SiLU-gated expert of n_shared_experts x moe_intermediate_size:
        s = sigmoid(h W_r)                            float32
        S_t = the k largest of s_t + b                b [E], no gradient, in
                                                      the choice alone;
                                                      n_group = topk_group = 1
        w_te = routed_scaling_factor * s_te / (sum_{e' in S_t} s_te' + 1e-20)
        FF(h_t) = sum_{e in S_t, e held} w_te Expert_e(h_t) + Shared(h_t)
    **This chip's share**: the tree holds experts ``first_expert`` to
    ``first_expert + held`` of E (``held`` is the expert matrices' first
    axis); the router, the bias, the choice and the renormalisation are over
    all E, the sum over the held ones alone plus the shared expert, and that
    partial sum goes on to the next layer. Nothing stands in for the absent
    experts.
    loss: mean next-token cross-entropy over the vocabulary held; **no
    auxiliary term** (a).
    after the optimizer's step (a): with c_e the tokens expert e of a layer
    got in that step over all data-parallel ranks,
        d_e = rate * sign(mean(c) - c_e);   b <- b + d - mean(d)
    AdamW neither moves nor decays b.

Departures from "plain": each layer is wrapped in ``jax.checkpoint`` and its
attention runs over blocks of ``query_rows`` query rows, each under a
checkpoint of its own, so that one block's float32 logits (0.13 GB at 2048
tokens and 16 heads) are all that is held beside the state; the arithmetic
is unchanged.

It reads the parameter tree ``models/gpt.py::init_params`` makes (an MLA
layer's matrices under ``mla``) and how many leading layers are dense from
the published ``first_k_dense_replace`` handed in by the job; parameters are
the interface, the arithmetic is its own. The expert block, the dense
feed-forward, the biases' update and AdamW's first step are those of the
``gpt_window_moe_dp`` reference, whose equations they share term for term
(a sigmoid router over one group, a bias in the choice alone, weights
renormalised and scaled, an ungated shared expert). It imports nothing from
the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_window_moe_dp import (  # noqa: F401
    adamw_first_update_norm, bias_step_on_load, biases, expert_block,
    gated_ff, router_logits, updated_biases)

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half on all of the last axis; ``x`` ``[b, S, heads, d]``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def mla_qkv(h, p, positions, *, rope_theta: float, norm_eps: float):
    """``(q [b, S, H, dn + dr], k the same, v [b, S, H, dv])`` of an MLA
    mixer ``p`` on normed activations ``h``; the sizes are read off the
    matrices: ``r`` is the latent norm's width, ``dr`` what ``W_kv_a`` has
    beyond it, ``dn`` what a query head has beyond ``dr``."""
    rank = p["kv_norm"].shape[0]
    rot = p["wkv_a"].shape[1] - rank
    nope = p["wq"].shape[2] - rot
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    a = jnp.dot(h, p["wkv_a"], precision=HI)
    c = _rmsnorm(a[..., :rank], p["kv_norm"], norm_eps)
    kv = jnp.einsum("bsr,rhd->bshd", c, p["wkv_b"], precision=HI)
    q_rot = _rope(q[..., nope:], positions, rope_theta)
    k_rot = _rope(a[:, :, None, rank:], positions, rope_theta)  # one head
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.repeat(k_rot, kv.shape[2], axis=2)], axis=-1)
    return q, k, kv[..., nope:]


def _rows_attention(q, k, v, first):
    """Query rows ``first ..`` of every head against all keys: ``q`` ``[b,
    R, H, dk]``, ``k`` ``[b, S, H, dk]``, ``v`` ``[b, S, H, dv]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    i = first + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(i >= jnp.arange(k.shape[1])[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=HI)


def causal_attention(q, k, v, query_rows: int = 1024):
    """Causal softmax attention, ``[b, S, H, .]`` in and out, the logits
    made ``query_rows`` query rows at a time."""
    seq = q.shape[1]
    rows = min(query_rows, seq)
    return jnp.concatenate([
        jax.checkpoint(_rows_attention, static_argnums=3)(
            q[:, i:i + rows], k, v, i)
        for i in range(0, seq, rows)], axis=1)


def mla(h, p, positions, **shape):
    q, k, v = mla_qkv(h, p, positions, **shape)
    return jnp.einsum("bshd,hde->bse", causal_attention(q, k, v), p["wo"],
                      precision=HI)


def _layer(x, p, positions, *, dense, top_k, route_scale, first_expert,
           rope_theta, norm_eps):
    h = _rmsnorm(x, p["mla_norm"], norm_eps)
    x = x + mla(h, p["mla"], positions, rope_theta=rope_theta,
                norm_eps=norm_eps)
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    if dense:
        return x + gated_ff(h, p["w_gate"], p["w_up"], p["w_down"]), None
    y, counts = expert_block(h.reshape(-1, h.shape[-1]), p["moe"], top_k,
                             route_scale, first_expert)
    return x + y.reshape(x.shape), counts


def shard_loss(params, tokens, targets, positions, *, dense_layers: int,
               norm_eps: float, **layer):
    """``(loss, parts)``: ``parts`` holds ``counts`` ``[expert layers, E]``.
    ``layer`` holds ``top_k``, ``route_scale``, ``first_expert`` and
    ``rope_theta``."""
    x = params["embed"][tokens]
    counts = []
    for i, p in enumerate(params["layers"]):
        x, c = jax.checkpoint(
            lambda x, p, dense=i < dense_layers: _layer(
                x, p, positions, dense=dense, norm_eps=norm_eps, **layer))(
                    x, p)
        if c is not None:
            counts.append(c)
    x = _rmsnorm(x, params["out_norm"], norm_eps)
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ev->bsv", x, params["lm_head"], precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)
    return loss, {"counts": jnp.stack(counts)}


def loss_and_grad(params, tokens, targets, positions, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss, tokens per expert summed over the shards ``[expert
    layers, E]``, and the mean gradient (the biases' is zero: they are in
    the choice alone)."""
    fn = jax.jit(lambda p, *data: jax.value_and_grad(
        lambda q: shard_loss(q, *data, **model), has_aux=True)(p))
    counts = []

    def one(p, *data):
        (loss, aux), grad = fn(p, *data)
        counts.append(jax.device_get(aux["counts"]))
        return loss, grad

    loss, grad = shards.loss_and_grad(one, params, tokens, targets, positions)
    return loss, sum(counts), grad
