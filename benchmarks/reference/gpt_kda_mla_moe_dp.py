"""Plain float32 reference of the ``gpt_kda_mla_moe_dp`` job: a decoder whose
mixers are Kimi delta attention (KDA: a delta-rule state that decays a key
channel, from a gate bounded below) five layers in six and gated latent
attention (MLA) the sixth, over one leading dense feed-forward and then
expert blocks whose sigmoid router, under a selection bias, chooses inside
the best groups of experts (``model_type: bailing_hybrid``, Ling-3.0-flash),
its loss, gradient, AdamW first step and the bias's update.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
chunk, no sort and no grouped matmul: KDA is the recurrence one token a step
(a ``lax.scan`` over tokens), MLA has ``S x S`` logits under the causal mask,
the grouped choice is written plainly, and every held expert is applied to
every token. Written from the published configuration's keys; what is no key
of it is from the Kimi Linear report (arXiv:2510.26692), flash-linear-
attention's lower-bound gate, the DeepSeek-V3 report and
``modeling_deepseek_v3.py`` as remembered (there is no network here) and is
listed under ``assumed`` in the configuration file, (a) below. The equations,
``H`` heads, ``K`` = ``V`` = ``head_dim`` a KDA head, hidden ``E``::

    RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w                # plain weight
    per layer:  x = x + Mixer(N1(x));  x = x + FF(N2(x))
    logits = W_head RMSNorm(x_L)                                 # untied head

    KDA(h), no bias anywhere:
        [q | k | v] = SiLU(conv4(h W_qkv))     causal depthwise, 4 taps, the
                                               last tap reads the token itself
        q_h = q_h / sqrt(|q_h|^2 + 1e-6) * K^-0.5
        k_h = k_h / sqrt(|k_h|^2 + 1e-6)
        g_t = lower_bound * sigmoid(exp(A_log_h) * (h_t W_f + dt_bias))    (a)
            a key channel, in (lower_bound, 0); W_f at full rank [E, H K]
        alpha_t = exp(g_t);  beta_t = sigmoid(h_t W_beta)        a head
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        S_0 = 0, S in R^{K x V};   o_t = S_t^T q_t
        KDA(h) = [RMSNorm_V(o_h; w) * sigmoid((h W_g)_h)]_h W_o  one gate a
                                                                 head (a)

    MLA(h): ``gpt_mla_moe_dp``'s reference (no latent on the query side, the
    latent normed, one rotary key a token for all heads, rotate-half at base
    rope_theta, scores over qk_nope + qk_rope dimensions scaled by one over
    its root), and on its output, before W_o, o_h * sigmoid((h W_g)_h)    (a)

    FF, layers 0 .. first_k_dense_replace - 1:  W_d(silu(W_g h) * W_u h)
    FF, every other layer: E router outputs in G groups of E / G neighbours:
        s = sigmoid(h W_r)   float32;   z = s + b    b [E], in the choice alone
        group score = the sum of the group's two largest z
        the kept groups = the topk_group largest group scores
        S_t = the k largest z among the kept groups' experts
        w_te = routed_scaling_factor * s_te / (sum_{e' in S_t} s_te' + 1e-20)
        FF(h_t) = sum_{e in S_t, e held} w_te Expert_e(h_t) + Shared(h_t)
    **This chip's share**: the tree holds experts ``first_expert`` to
    ``first_expert + held`` of E; the router, the bias, the groups, the
    choice and the renormalisation are over all E, the sum over the held
    ones alone plus the shared expert, and that partial sum goes on to the
    next layer. Nothing stands in for the absent experts.
    loss: mean next-token cross-entropy over the vocabulary held; **no
    auxiliary term** (a: ``seq_aux`` names one, the config has no
    coefficient); no multi-token-prediction module (its loss is weighed 0).
    after the optimizer's step: ``gpt_window_moe_dp``'s bias update.

Departures from "plain": each layer is wrapped in ``jax.checkpoint``,
MLA's logits are made a block of query rows at a time, as in
``gpt_mla_moe_dp``'s reference, and KDA's steps run 64 at a time under a
checkpoint of their own; the arithmetic is unchanged.

It reads the parameter tree ``models/gpt.py::init_params`` makes (a KDA
layer's matrices under ``kda``, an MLA layer's under ``mla``); parameters
are the interface, the arithmetic is its own. The dense feed-forward, the
experts' form, the biases' update and AdamW's first step are those of the
``gpt_window_moe_dp`` reference, MLA's projections and attention those of
``gpt_mla_moe_dp``'s. It imports nothing from the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_mla_moe_dp import causal_attention, mla_qkv
from benchmarks.reference.gpt_window_moe_dp import (  # noqa: F401
    adamw_first_update_norm, bias_step_on_load, biases, gated_ff,
    router_logits, updated_biases)

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _unit(t, scale=1.0):
    return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6) \
        * scale


def _conv_silu(x, w):
    """Causal depthwise convolution of ``x`` ``[b, S, C]`` with ``w``
    ``[taps, C]`` (tap ``taps - 1`` reads the token itself), then SiLU."""
    taps, seq = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + seq] * w[i] for i in range(taps)))


def kda_recurrence(q, k, v, g, beta, block: int = 64):
    """The state one token a step: ``q``, ``k``, ``g`` ``[b, S, H, K]``,
    ``v`` ``[b, S, H, V]``, ``beta`` ``[b, S, H]`` -> ``o`` ``[b, S, H,
    V]``. (The steps run ``block`` at a time under a checkpoint of their
    own, so that the backward pass holds one block's states, 2 MB a token at
    32 heads of 128 x 128, and not the sequence's; a step is the same.)"""
    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None] * state          # Diag(alpha) S
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - seen),
            precision=HI)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    seq = q.shape[1]
    block = block if seq % block == 0 else seq
    start = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[3:], k.dtype)
    _, o = lax.scan(
        jax.checkpoint(lambda state, steps: lax.scan(step, state, steps)),
        start, tuple(jnp.moveaxis(t, 1, 0).reshape(
            (seq // block, block) + t.shape[:1] + t.shape[2:])
            for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((seq,) + o.shape[2:]), 0, 1)


def kda(h, p, *, lower_bound: float, norm_eps: float):
    """A KDA mixer ``p`` on normed activations ``h`` ``[b, S, E]``; the
    sizes are read off the matrices (``H`` is ``A_log``'s length, ``V`` the
    output norm's, ``K`` what ``W_f`` gives a head)."""
    heads, width = p["A_log"].shape[0], p["norm"].shape[0]
    key_dim = p["w_f"].shape[1] // heads
    lead = h.shape[:2]
    qkv = _conv_silu(jnp.dot(h, p["w_qkv"], precision=HI), p["conv_w"])
    q, k, v = jnp.split(qkv, [heads * key_dim, 2 * heads * key_dim], axis=-1)
    q = _unit(q.reshape(lead + (heads, key_dim)), key_dim ** -0.5)
    k = _unit(k.reshape(lead + (heads, key_dim)))
    v = v.reshape(lead + (heads, width))
    f = (jnp.dot(h, p["w_f"], precision=HI) + p["dt_bias"]).reshape(
        lead + (heads, key_dim))
    g = lower_bound * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    beta = jax.nn.sigmoid(jnp.dot(h, p["w_beta"], precision=HI))
    o = kda_recurrence(q, k, v, g, beta)
    open_ = jax.nn.sigmoid(jnp.dot(h, p["w_gate"], precision=HI))
    y = _rmsnorm(o, p["norm"], norm_eps) * open_[..., None]
    return jnp.dot(y.reshape(lead + (-1,)), p["out_proj"], precision=HI)


def gated_mla(h, p, positions, *, rope_theta: float, norm_eps: float):
    q, k, v = mla_qkv(h, p, positions, rope_theta=rope_theta,
                      norm_eps=norm_eps)
    open_ = jax.nn.sigmoid(jnp.dot(h, p["w_gate"], precision=HI))
    return jnp.einsum("bshd,hde->bse",
                      causal_attention(q, k, v) * open_[..., None], p["wo"],
                      precision=HI)


def grouped_choice(leaning, top_k: int, groups: int, kept: int):
    """``[T, k]`` expert indices: the ``k`` largest leaning scores among the
    experts of the ``kept`` groups whose two largest leaning scores sum
    highest; a tie goes to the lower index."""
    tokens, experts = leaning.shape
    by_group = leaning.reshape(tokens, groups, experts // groups)
    two = lax.top_k(by_group, 2)[0]
    _, best = lax.top_k(two[..., 0] + two[..., 1], kept)
    keep = jnp.sum(jax.nn.one_hot(best, groups, dtype=leaning.dtype), axis=1)
    masked = jnp.where(jnp.repeat(keep, experts // groups, axis=1) > 0,
                       leaning, -jnp.inf)
    return lax.top_k(masked, top_k)[1]


def expert_block(h, m, top_k: int, route_scale: float, first_expert: int,
                 groups: int, kept: int):
    """``h`` ``[T, d]``, ``m`` the block's parameters -> ``(y [T, d], tokens
    per expert [E])``; ``y`` is the held experts' part of the sum plus the
    shared expert."""
    experts, held = m["router"].shape[-1], m["w_up"].shape[0]
    scores = jax.nn.sigmoid(router_logits(h, m["router"]))
    top_e = grouped_choice(
        lax.stop_gradient(scores + m["router_bias"]), top_k, groups, kept)
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
    return y + gated_ff(h, s["w_gate"], s["w_up"], s["w_down"]), \
        jnp.sum(chosen, axis=0)


def _layer(x, p, positions, *, top_k, route_scale, first_expert, groups,
           kept, rope_theta, norm_eps, lower_bound):
    """A layer says what it is by what it holds: ``kda`` or ``mla``, ``moe``
    or a dense feed-forward's three matrices."""
    if "kda" in p:
        x = x + kda(_rmsnorm(x, p["kda_norm"], norm_eps), p["kda"],
                    lower_bound=lower_bound, norm_eps=norm_eps)
    else:
        x = x + gated_mla(_rmsnorm(x, p["mla_norm"], norm_eps), p["mla"],
                          positions, rope_theta=rope_theta,
                          norm_eps=norm_eps)
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    if "moe" not in p:
        return x + gated_ff(h, p["w_gate"], p["w_up"], p["w_down"]), None
    y, counts = expert_block(h.reshape(-1, h.shape[-1]), p["moe"], top_k,
                             route_scale, first_expert, groups, kept)
    return x + y.reshape(x.shape), counts


def shard_loss(params, tokens, targets, positions, *, norm_eps: float,
               **layer):
    """``(loss, parts)``: ``parts`` holds ``counts`` ``[expert layers, E]``.
    ``layer`` holds ``top_k``, ``route_scale``, ``first_expert``,
    ``groups``, ``kept``, ``rope_theta`` and ``lower_bound``."""
    x = params["embed"][tokens]
    counts = []
    for p in params["layers"]:
        x, c = jax.checkpoint(lambda x, p: _layer(
            x, p, positions, norm_eps=norm_eps, **layer))(x, p)
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
