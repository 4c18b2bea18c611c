"""Plain float32 reference of the ``gpt_window_moe_dp`` job: a decoder that
mixes sliding-window and full attention layers, SiLU-gated dense
feed-forwards before expert blocks with a sigmoid router under a selection
bias and one ungated shared expert (``model_type: afmoe``, Trinity-Mini), its
loss, gradient, AdamW first step and the bias's update.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
sort and no grouped matmul: ``S x S`` logits under the band mask, every held
expert applied to every token. Written from the published configuration's
keys; what is no key of it is from transformers' ``modeling_afmoe.py`` and
torchtitan's ``MoE`` as remembered (there is no network here) and is listed
under ``assumed`` in the configuration file, (a) below. The equations::

    RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w                # plain weight
    x_0 = E[tokens] * sqrt(d)                                    # (a) mup
    per layer, four norms:  x = x + N2(Attn(N1(x)));  x = x + N4(FF(N3(x)))
    logits = W_head RMSNorm(x_L)                                 # untied head

    Attn(h), H query heads and Hkv key/value heads of D, no bias:
        q, k, v, g = h W_q, h W_k, h W_v, h W_g       (W_g as wide as W_q, (a))
        q, k through RMSNorm a head (one weight of D each, (a))
        on a sliding_attention layer ONLY: the rotary embedding (rotate-half,
            base theta, all D dimensions) and the mask 0 <= i - j < W (a
            query sees itself and the W - 1 keys before it);
        on a full_attention layer: no position embedding, the causal mask (a)
        logits over sqrt(D), key/value heads repeated explicitly
        o = (softmax(.) v * sigmoid(g)) W_o

    FF, layers 0 .. num_dense_layers - 1:  W_d(silu(W_g h) * W_u h)
    FF, every other layer, E router outputs, k a token, one shared expert:
        s = sigmoid(h W_r)                            float32
        S_t = the k largest of s_t + b                b [E], no gradient,
                                                      in the choice alone
        w_te = route_scale * s_te / (sum_{e' in S_t} s_te' + 1e-20)
        FF(h_t) = sum_{e in S_t, e held} w_te Expert_e(h_t) + Shared(h_t)
        Expert_e(h) = W_down,e(silu(W_gate,e h) * W_up,e h); the shared
        expert the same, added as it is, with no gate of its own (a)
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
attention runs one key/value head's group of query heads at a time, each
under a checkpoint of its own, so that the ``S x S`` float32 logits of one
group (0.5 GB at 4096 tokens and 8 heads) are all that is held beside the
state; the arithmetic is unchanged.

It reads the parameter tree ``models/gpt.py::init_params`` makes, and each
layer's kind from the published ``layer_types`` and ``num_dense_layers``
handed in by the job; parameters are the interface, the arithmetic is its
own. It imports nothing from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def band_mask(n: int, window=None):
    """``[n, n]`` booleans, query by key: ``0 <= i - j`` and, under a
    window, ``i - j < window``."""
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    keep = i >= j
    return keep if window is None else keep & (i - j < window)


def _group_attention(q, k, v, keep):
    """One key/value head and its query heads: ``q`` ``[b, S, g, D]``, ``k``
    and ``v`` ``[b, S, D]``."""
    s = jnp.einsum("bqhd,bkd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=HI)


def attention(h, p, positions, *, window, rope_theta, norm_eps):
    """``window`` None: a full_attention layer (causal, no position
    embedding); else a sliding_attention layer."""
    qg = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    q, gate = jnp.split(qg, 2, axis=-1)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    q = _rmsnorm(q, p["q_norm"], norm_eps)
    k = _rmsnorm(k, p["k_norm"], norm_eps)
    if window is not None:
        q = _rope(q, positions, rope_theta)
        k = _rope(k, positions, rope_theta)
    keep = band_mask(q.shape[1], window)
    group = q.shape[2] // k.shape[2]
    a = jnp.concatenate([
        jax.checkpoint(_group_attention)(
            q[:, :, g * group:(g + 1) * group], k[:, :, g], v[:, :, g], keep)
        for g in range(k.shape[2])], axis=2)
    return jnp.einsum("bshd,hde->bse", a * jax.nn.sigmoid(gate), p["wo"],
                      precision=HI)


def gated_ff(h, w_gate, w_up, w_down):
    hidden = jax.nn.silu(jnp.dot(h, w_gate, precision=HI)) \
        * jnp.dot(h, w_up, precision=HI)
    return jnp.dot(hidden, w_down, precision=HI)


def router_logits(h, router):
    """What a router ``[d, E]`` gives on activations ``h`` ``[T, d]``: a
    float32 product at the highest precision, whatever ``h`` came as."""
    return jnp.dot(h.astype(jnp.float32), router, precision=HI)


def expert_block(h, m, top_k: int, route_scale: float,
                 first_expert: int = 0):
    """``h`` ``[T, d]``, ``m`` the block's parameters -> ``(y [T, d], tokens
    per expert [E])``; ``y`` is the held experts' part of the sum plus the
    shared expert."""
    experts, held = m["router"].shape[-1], m["w_up"].shape[0]
    scores = jax.nn.sigmoid(router_logits(h, m["router"]))
    _, top_e = lax.top_k(scores + lax.stop_gradient(m["router_bias"]),
                         top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, experts, dtype=h.dtype), axis=1)
    weights = route_scale * chosen * scores / (
        jnp.sum(chosen * scores, axis=-1, keepdims=True) + 1e-20)
    weights = weights[:, first_expert:first_expert + held]      # [T, held]
    gate = jnp.einsum("td,edm->etm", h, m["w_gate"], precision=HI)
    up = jnp.einsum("td,edm->etm", h, m["w_up"], precision=HI)
    out = jnp.einsum("etm,emd->etd", jax.nn.silu(gate) * up, m["w_down"],
                     precision=HI)
    y = jnp.einsum("te,etd->td", weights, out, precision=HI)
    s = m["shared"]
    y = y + gated_ff(h, s["w_gate"], s["w_up"], s["w_down"])
    return y, jnp.sum(chosen, axis=0)


def _layer(x, p, positions, *, window, dense, top_k, route_scale,
           first_expert, rope_theta, norm_eps):
    h = _rmsnorm(x, p["attn_norm"], norm_eps)
    x = x + _rmsnorm(attention(h, p, positions, window=window,
                               rope_theta=rope_theta, norm_eps=norm_eps),
                     p["mixer_post_norm"], norm_eps)
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    if dense:
        y, counts = gated_ff(h, p["w_gate"], p["w_up"], p["w_down"]), None
    else:
        y, counts = expert_block(h.reshape(-1, h.shape[-1]), p["moe"], top_k,
                                 route_scale, first_expert)
        y = y.reshape(x.shape)
    return x + _rmsnorm(y, p["mlp_post_norm"], norm_eps), counts


def shard_loss(params, tokens, targets, positions, *, windows,
               dense_layers: int, norm_eps: float, **layer):
    """``(loss, parts)``: ``parts`` holds ``counts`` ``[expert layers, E]``.
    ``windows`` has each layer's window (None: a full_attention layer);
    ``layer`` holds ``top_k``, ``route_scale``, ``first_expert`` and
    ``rope_theta``."""
    x = params["embed"][tokens] * math.sqrt(params["embed"].shape[1])
    counts = []
    for i, (p, window) in enumerate(zip(params["layers"], windows,
                                        strict=True)):
        x, c = jax.checkpoint(
            lambda x, p, window=window, dense=i < dense_layers: _layer(
                x, p, positions, window=window, dense=dense,
                norm_eps=norm_eps, **layer))(x, p)
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


def biases(tree) -> list:
    """The selection biases of a parameter tree, one ``[E]`` an expert
    layer."""
    return [p["moe"]["router_bias"] for p in tree["layers"] if "moe" in p]


def updated_biases(params, counts, rate: float) -> list:
    """The selection biases after the step's update from ``counts``
    ``[expert layers, E]``: ``b + d - mean(d)``, ``d = rate * sign(mean(c) -
    c)``."""
    out = []
    for b, c in zip(biases(params), jnp.asarray(counts, jnp.float32),
                    strict=True):
        d = rate * jnp.sign(jnp.mean(c) - c)
        out.append(b + d - jnp.mean(d))
    return out


def bias_step_on_load(before, after, counts) -> float:
    """What the biases' update did, weighed by the load it answers: ``sum_e
    (b'_e - b_e) c_e / sum_e c_e``, summed over the expert layers. The
    update takes ``rate`` from every expert over the mean and gives it to
    every one under it, so this is ``-rate`` times the counts' mean absolute
    deviation over their mean, a layer: below nothing, a few ``rate`` in
    size, and it does not hang on which way an expert at the mean went. An
    update left out reads nothing, one of the wrong sign the opposite, one
    from other counts (the bias out of the choice) something else."""
    return float(sum(
        jnp.sum((a - b) * c) / jnp.sum(c)
        for b, a, c in zip(before, after, jnp.asarray(counts, jnp.float32),
                           strict=True)))


def adamw_first_update_norm(params, grad, lr: float, weight_decay: float,
                            eps: float) -> float:
    """The norm of what AdamW's first step adds to the parameters, summed
    leaf by leaf: ``-lr (g / (|g| + eps) + weight_decay p)``, and **nothing
    for a selection bias**, which AdamW neither moves nor decays."""
    def leaf(path, p, g):
        if getattr(path[-1], "key", None) == "router_bias":
            return 0.0
        return float(jnp.sum(jnp.square(
            lr * (g / (jnp.abs(g) + eps) + weight_decay * p))))

    return sum(jax.tree.leaves(jax.tree_util.tree_map_with_path(
        leaf, params, grad))) ** 0.5
