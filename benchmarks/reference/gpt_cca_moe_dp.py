"""Plain float32 reference of the ``gpt_cca_moe_dp`` job: a decoder whose
every layer is a CCA attention sublayer (attention in a compressed latent,
q and k mixed by two stacked causal convolutions) and an expert sublayer
with one expert a token chosen by an MLP router that carries a state from
layer to layer, each sublayer joined to the stream under a learned scaling
(``model_type: zaya``, ZAYA1-8B), its loss, gradient, AdamW first step and
the selection bias's update.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
sort and no grouped matmul: ``S x S`` logits, the convolutions as shifted
sums, every held expert applied to every token. Written from the published
configuration's keys; what is no key of it is from the ZAYA1 report
(arXiv:2511.17127), the CCA paper (arXiv:2510.04476) and the ``zaya`` model
code as remembered (there is no network here) and is listed under
``assumed`` in the configuration file, (a) below. ``E`` the width, ``Hq``
query and ``Hk`` key/value heads of ``D``, ``G = Hq / Hk``, ``R`` the
router's width. The equations::

    RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w                # plain weight
    a sublayer f with its norm N and four vectors (a):
        x <- a_r * (x + b_r) + a_h * (f(N(x)) + b_h)
    a layer: the CCA sublayer, then the expert sublayer
    logits = E^T RMSNorm(x_L)                                    # tied head

    CCA(h):
        [q0 | k0] = h W_qk                    [E, Hq D + Hk D], no bias
        v = [ h W_v1 | shift(h) W_v2 ]        shift(h)_t = h_{t-1}, h_{-1} = 0;
            split into Hk heads of D: the first half of the heads carry the
            token's value, the second half the value of the token before (a)
        u  = [q0 | k0]
        c1_t = b0 + sum_k w0[k] u_{t-(K0-1)+k}                depthwise, K0 taps
        c2_t = b1 + sum_k W1[k, g] c1_{t-(K1-1)+k, g}         a head g of the
            Hq + Hk at a time, D -> D channels, K1 taps; zeros before the
            start in both, no activation between or after (a)
        qm_h = (q0_h + k0_{h // G}) / 2
        km_g = mean over the G query heads h of group g of qm_h          (a)
        q = c2[:Hq D] + qm        k = c2[Hq D:] + km
        q <- sqrt(D) q / sqrt(|q|^2 + 1e-6)          a head
        k <- sqrt(D) exp(t_g) k / sqrt(|k|^2 + 1e-6) a head, t [Hk]      (a)
        rotary embedding (rotate-half) on the first rotary_dim dimensions
            of a head, base theta, after the norm
        causal softmax attention, logits over sqrt(D), key/value heads
            repeated explicitly;  out = a W_o

    Router of expert sublayer l on h = N(x), state z_{l-1} (a):
        z_l = h W_d + b_d  (+ g_l * z_{l-1} for every sublayer but the
              first of the layers run)        z_l goes on to sublayer l + 1
        s = RMSNorm(z_l)
        r = W_3 gelu(W_2 gelu(W_1 s + b_1) + b_2)       gelu exact (erf)
        p = softmax(r);   e_t = argmax(p_t + b)    b [experts], no gradient,
                                                   in the choice alone
        y_t = p_{t, e_t} Expert_{e_t}(h_t)         not renormalised
        Expert_e(h) = W_down,e(silu(W_gate,e h) * W_up,e h)
    **This chip's share**: the tree holds experts ``first_expert`` to
    ``first_expert + held`` of the router's (``held`` is the expert
    matrices' first axis); the router, the bias and the choice are over all
    of them, and a token whose expert is held elsewhere gets nothing from
    this sublayer. Nothing stands in for the absent experts.
    loss: mean next-token cross-entropy over the vocabulary held; **no
    auxiliary term**.
    after the optimizer's step, the bias's update as ``gpt_window_moe_dp``'s
    reference has it (this repo's sign rule; AdamW neither moves nor decays
    b).

It reads the parameter tree ``models/gpt.py::init_params`` makes;
parameters are the interface, the arithmetic is its own. It imports nothing
from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_window_moe_dp import (  # noqa: F401
    adamw_first_update_norm, bias_step_on_load, biases, updated_biases)

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta, rotary_dim):
    """Rotate-half on the first ``rotary_dim`` dimensions of a head."""
    turned, kept = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = turned[..., :half], turned[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang), kept],
                           axis=-1)


def shift(t, tokens: int = 1):
    """``t`` ``[b, S, ...]`` with position ``i`` holding what ``i - tokens``
    held, zeros before the start."""
    if tokens == 0:
        return t
    return jnp.concatenate([jnp.zeros_like(t[:, :tokens]), t[:, :-tokens]],
                           axis=1)


def depthwise_conv(u, w, b):
    """``u`` ``[b, S, C]``, ``w`` ``[K, C]``, ``b`` ``[C]``: tap ``K - 1``
    reads the token itself."""
    taps = w.shape[0]
    return b + sum(w[k] * shift(u, taps - 1 - k) for k in range(taps))


def grouped_conv(c, w, b):
    """``c`` ``[b, S, groups, D]``, ``w`` ``[K, groups, D in, D out]``, ``b``
    ``[groups D]`` -> ``[b, S, groups D]``."""
    taps = w.shape[0]
    out = sum(jnp.einsum("bsgi,gio->bsgo", shift(c, taps - 1 - k), w[k],
                         precision=HI) for k in range(taps))
    return out.reshape(*c.shape[:2], -1) + b


def cca_qkv(h, p, positions, *, heads: int, kv_heads: int, rope_theta: float,
            rotary_dim: int):
    """``(q [b, S, Hq, D], k, v [b, S, Hk, D])`` as attention takes them."""
    b, s, _ = h.shape
    dim = p["wo"].shape[0] // heads
    group, q_dim = heads // kv_heads, heads * dim
    u = jnp.einsum("bse,ef->bsf", h, p["wqk"], precision=HI)
    half = p["wv"].shape[1] // 2
    v = jnp.concatenate([
        jnp.einsum("bse,ef->bsf", h, p["wv"][:, :half], precision=HI),
        jnp.einsum("bse,ef->bsf", shift(h), p["wv"][:, half:], precision=HI)],
        axis=-1).reshape(b, s, kv_heads, dim)
    c1 = depthwise_conv(u, p["conv0_w"], p["conv0_b"])
    c2 = grouped_conv(c1.reshape(b, s, heads + kv_heads, dim), p["conv1_w"],
                      p["conv1_b"])
    q0 = u[..., :q_dim].reshape(b, s, kv_heads, group, dim)
    k0 = u[..., q_dim:].reshape(b, s, kv_heads, 1, dim)
    qm = (q0 + k0) / 2
    q = c2[..., :q_dim].reshape(b, s, heads, dim) \
        + qm.reshape(b, s, heads, dim)
    k = c2[..., q_dim:].reshape(b, s, kv_heads, dim) + jnp.mean(qm, axis=3)

    def unit(t):
        return math.sqrt(dim) * t / jnp.sqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = unit(q)
    k = unit(k) * jnp.exp(p["temp"])[:, None]
    return (_rope(q, positions, rope_theta, rotary_dim),
            _rope(k, positions, rope_theta, rotary_dim), v)


def cca(h, p, positions, **shape):
    q, k, v = cca_qkv(h, p, positions, **shape)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return jnp.einsum("bsf,fe->bse", a.reshape(*a.shape[:2], -1), p["wo"],
                      precision=HI)


def router(h, r, state, eps: float):
    """``(outputs [T, experts], z [T, R])`` of the router ``r`` on ``h``
    ``[T, E]``; ``state`` the ``z`` of the expert sublayer before, or None
    for the first."""
    z = jnp.dot(h.astype(jnp.float32), r["down"], precision=HI) + r["down_b"]
    if state is not None:
        z = z + r["carry"] * state
    s = _rmsnorm(z, r["norm"], eps)
    s = jax.nn.gelu(jnp.dot(s, r["w1"], precision=HI) + r["b1"],
                    approximate=False)
    s = jax.nn.gelu(jnp.dot(s, r["w2"], precision=HI) + r["b2"],
                    approximate=False)
    return jnp.dot(s, r["w3"], precision=HI), z


def expert_block(h, m, state, eps: float, first_expert: int = 0):
    """``h`` ``[T, E]``, ``m`` the sublayer's parameters -> ``(y [T, E],
    tokens per expert [experts], z)``; ``y`` is the held experts' part: a
    token whose one expert is not among them gets zeros."""
    held = m["w_up"].shape[0]
    out, z = router(h, m["router"], state, eps)
    experts = out.shape[-1]
    p = jax.nn.softmax(out, axis=-1)
    _, top_e = lax.top_k(p + lax.stop_gradient(m["router_bias"]), 1)
    chosen = jax.nn.one_hot(top_e[:, 0], experts, dtype=h.dtype)
    weights = (chosen * p)[:, first_expert:first_expert + held]  # [T, held]
    gate = jnp.einsum("td,edm->etm", h, m["w_gate"], precision=HI)
    up = jnp.einsum("td,edm->etm", h, m["w_up"], precision=HI)
    each = jnp.einsum("etm,emd->etd", jax.nn.silu(gate) * up, m["w_down"],
                      precision=HI)
    y = jnp.einsum("te,etd->td", weights, each, precision=HI)
    return y, jnp.sum(chosen, axis=0), z


def residual(x, f, r):
    return r["stream_scale"] * (x + r["stream_bias"]) \
        + r["branch_scale"] * (f + r["branch_bias"])


def _layer(x, p, positions, state, *, first_expert, norm_eps, **shape):
    h = _rmsnorm(x, p["cca_norm"], norm_eps)
    x = residual(x, cca(h, p["cca"], positions, **shape), p["mixer_res"])
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    y, counts, state = expert_block(h.reshape(-1, h.shape[-1]), p["moe"],
                                    state, norm_eps, first_expert)
    return residual(x, y.reshape(x.shape), p["mlp_res"]), counts, state


def shard_loss(params, tokens, targets, positions, **model):
    """``(loss, parts)``: ``parts`` holds ``counts`` ``[layers, experts]``.
    ``model`` holds ``heads``, ``kv_heads``, ``rope_theta``, ``rotary_dim``,
    ``first_expert`` and ``norm_eps``."""
    x = params["embed"][tokens]
    counts, state = [], None
    for p in params["layers"]:
        x, c, state = _layer(x, p, positions, state, **model)
        counts.append(c)
    x = _rmsnorm(x, params["out_norm"], model["norm_eps"])
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ve->bsv", x, params["embed"], precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)
    return loss, {"counts": jnp.stack(counts)}


def loss_and_grad(params, tokens, targets, positions, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss, tokens per expert summed over the shards ``[layers,
    experts]``, and the mean gradient (the biases' is zero: they are in the
    choice alone)."""
    fn = jax.jit(lambda p, *data: jax.value_and_grad(
        lambda q: shard_loss(q, *data, **model), has_aux=True)(p))
    counts = []

    def one(p, *data):
        (loss, aux), grad = fn(p, *data)
        counts.append(jax.device_get(aux["counts"]))
        return loss, grad

    loss, grad = shards.loss_and_grad(one, params, tokens, targets, positions)
    return loss, sum(counts), grad
