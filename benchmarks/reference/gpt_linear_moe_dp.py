"""Plain float32 reference of the ``gpt_linear_moe_dp`` job: a decoder whose
layers are gated-delta-rule linear attention or gated softmax attention,
each followed by an expert block with a shared expert (``model_type:
qwen3_next``), its loss, gradient and AdamW first step.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
chunking, no sort and no grouped matmul: the linear-attention recurrence is a
``lax.scan`` over time, one token a step, and every held expert is applied to
every token. Written from the published configuration's keys and
``modeling_qwen3_next.py`` as remembered; there is no network here. The
equations::

    RMSNorm_0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)
    per layer:  x = x + mixer(RMSNorm_0(x));  x = x + experts(RMSNorm_0(x))
    logits = W_head RMSNorm_0(x_L)                           # untied head

    linear-attention mixer (Hk key heads of K, Hv value heads of V; value
    head h reads key head h // (Hv / Hk)):
        [q | k | v | z] = h W_qkvz        (Hk K | Hk K | Hv V | Hv V)
        [b | a] = h W_ba                  (Hv | Hv)
        [q | k | v] = silu(conv([q | k | v])): causal, depthwise, 4 taps, no
            bias, conv(u)_t = sum_j w_j u_{t-3+j}, zeros before the start
        q = q / sqrt(|q|^2 + 1e-6) / sqrt(K);  k = k / sqrt(|k|^2 + 1e-6)
        beta = sigmoid(b);  alpha = exp(-exp(A_log) softplus(a + dt_bias))
        state S in R^{K x V} a value head, S_0 = 0:
            S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
            o_t = S_t^T q_t
        y = (o / sqrt(mean(o^2) + eps) * w_norm) * silu(z)   # a value head
        out = W_out y

    full-attention mixer: [q | gate] = h W_q a head, k, v = h W_k, h W_v;
        q, k through RMSNorm_0 a head (one weight of the head's size);
        rotary embedding at base theta on the first rotary_dim dimensions
        (rotate-half within them), the rest untouched; causal softmax
        attention at 1/sqrt(D), key and value heads repeated explicitly;
        out = W_o (attention * sigmoid(gate))

    expert block, for tokens h: p = softmax(h W_r) over all E;
        S_t its k largest; w_te = p_te / sum_{e' in S_t} p_te';
        y_t = sum_{e in S_t, e held} w_te W_down,e(silu(W_gate,e h_t)
            * W_up,e h_t)
            + sigmoid(<h_t, w_sg>) W_down,s(silu(W_gate,s h_t) * W_up,s h_t)
    **This chip's share**: the tree holds experts ``first_expert`` to
    ``first_expert + held`` of E (``held`` is the expert matrices' first
    axis); the router, the choice and the renormalisation are over all E, the
    sum over the held ones alone, and that partial sum goes on to the next
    layer. Nothing stands in for the absent experts. The load-balance term is
    over all E: ``E sum_e f_e P_e``, ``f_e`` the share of tokens whose
    ``S_t`` holds ``e`` (a constant), ``P_e`` the mean of ``p_te``. The loss
    is the mean next-token cross-entropy plus ``load_balance_coef`` times the
    sum of that term over layers.

Departures from "plain": each layer is wrapped in ``jax.checkpoint`` so that
the time scan's per-step states (2 MB a token a layer at the published
widths) are held for one layer at a time; the arithmetic is unchanged. The
published ``in_proj_qkvz`` interleaves its columns by key head; the tree
holds them as ``[q | k | v | z]``, a permutation of columns.

It reads the parameter tree ``models/gpt.py::init_params`` makes (a layer
with a ``gdn`` entry is a linear-attention layer); parameters are the
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


def _rmsnorm0(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, positions, theta, rotary_dim):
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = rot[..., :half], rot[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang), rest],
                           axis=-1)


def _attention_mixer(h, p, positions, *, rope_theta, rotary_dim, norm_eps):
    qg = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    q, gate = jnp.split(qg, 2, axis=-1)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    q = _rope(_rmsnorm0(q, p["q_norm"], norm_eps), positions, rope_theta,
              rotary_dim)
    k = _rope(_rmsnorm0(k, p["k_norm"], norm_eps), positions, rope_theta,
              rotary_dim)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return jnp.einsum("bshd,hde->bse", a * jax.nn.sigmoid(gate), p["wo"],
                      precision=HI)


def recurrence(q, k, v, alpha, beta):
    """The gated delta rule itself, one token a step. ``q``, ``k`` ``[b, S,
    Hv, K]`` (already a value head each), ``v`` ``[b, S, Hv, V]``, ``alpha``
    and ``beta`` ``[b, S, Hv]`` -> ``o`` ``[b, S, Hv, V]``."""

    def step(state, now):
        q_t, k_t, v_t, a_t, b_t = now        # [b,H,K] [b,H,K] [b,H,V] [b,H]
        state = a_t[..., None, None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   b_t[..., None] * (v_t - seen),
                                   precision=HI)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    start = jnp.zeros(v.shape[:1] + v.shape[2:3] + k.shape[3:] + v.shape[3:],
                      v.dtype)
    _, o = lax.scan(step, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1)


def _linear_mixer(h, p, *, key_dim, norm_eps):
    heads = p["A_log"].shape[0]
    width = p["norm"].shape[0]
    conv_dim = p["conv_w"].shape[1]
    key_inner = (conv_dim - heads * width) // 2
    key_heads = key_inner // key_dim
    batch, seq = h.shape[:2]
    qkvz = jnp.einsum("bse,ef->bsf", h, p["in_proj"], precision=HI)
    qkv, z = jnp.split(qkvz, [conv_dim], axis=-1)
    b, a = jnp.split(jnp.einsum("bse,ef->bsf", h, p["in_proj_ba"],
                                precision=HI), 2, axis=-1)
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + seq] * p["conv_w"][j]
                          for j in range(taps)))
    q, k, v = jnp.split(qkv, [key_inner, 2 * key_inner], axis=-1)

    def heads_of(t):
        t = t.reshape(batch, seq, key_heads, key_dim)
        t = t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(t, heads // key_heads, axis=2)

    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    o = recurrence(heads_of(q) / math.sqrt(key_dim), heads_of(k),
                   v.reshape(batch, seq, heads, width), alpha,
                   jax.nn.sigmoid(b))
    y = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + norm_eps) \
        * p["norm"] * jax.nn.silu(z.reshape(o.shape))
    return jnp.einsum("bsf,fe->bse", y.reshape(batch, seq, heads * width),
                      p["out_proj"], precision=HI)


def expert_block(h, m, top_k: int, first_expert: int = 0):
    """``h`` ``[T, d]``, ``m`` the block's parameters -> ``(y [T, d],
    load-balance term, tokens per expert [E])``; ``y`` is the held experts'
    part of the sum plus, where the block has one, the shared expert."""
    experts, held = m["router"].shape[-1], m["w_up"].shape[0]
    probs = jax.nn.softmax(jnp.dot(h, m["router"], precision=HI), axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, experts, dtype=h.dtype), axis=1)
    weights = chosen * probs / jnp.sum(top_p, axis=-1, keepdims=True)
    weights = weights[:, first_expert:first_expert + held]      # [T, held]
    gate = jnp.einsum("td,edm->etm", h, m["w_gate"], precision=HI)
    up = jnp.einsum("td,edm->etm", h, m["w_up"], precision=HI)
    out = jnp.einsum("etm,emd->etd", jax.nn.silu(gate) * up, m["w_down"],
                     precision=HI)
    y = jnp.einsum("te,etd->td", weights, out, precision=HI)
    if "shared" in m:
        y = y + shared_expert(h, m["shared"])
    counts = jnp.sum(chosen, axis=0)
    load_balance = experts * jnp.sum(
        lax.stop_gradient(counts / h.shape[0]) * jnp.mean(probs, axis=0))
    return y, load_balance, counts


def shared_expert(h, s):
    hidden = jax.nn.silu(jnp.dot(h, s["w_gate"], precision=HI)) \
        * jnp.dot(h, s["w_up"], precision=HI)
    return jax.nn.sigmoid(jnp.dot(h, s["gate"], precision=HI))[:, None] \
        * jnp.dot(hidden, s["w_down"], precision=HI)


def _layer(x, p, positions, *, top_k, first_expert, key_dim, rope_theta,
           rotary_dim, norm_eps):
    if "gdn" in p:
        mixed = _linear_mixer(_rmsnorm0(x, p["gdn_norm"], norm_eps),
                              p["gdn"], key_dim=key_dim, norm_eps=norm_eps)
    else:
        mixed = _attention_mixer(
            _rmsnorm0(x, p["attn_norm"], norm_eps), p, positions,
            rope_theta=rope_theta, rotary_dim=rotary_dim, norm_eps=norm_eps)
    x = x + mixed
    h = _rmsnorm0(x, p["mlp_norm"], norm_eps)
    y, load_balance, counts = expert_block(
        h.reshape(-1, h.shape[-1]), p["moe"], top_k, first_expert)
    return x + y.reshape(x.shape), load_balance, counts


def shard_loss(params, tokens, targets, positions, *,
               load_balance_coef: float, norm_eps: float, **layer):
    """``(loss, parts)``: ``parts`` holds ``cross_entropy``, ``load_balance``
    (the sum over layers) and ``counts`` ``[layers, E]``. ``layer`` holds
    ``top_k``, ``first_expert``, ``key_dim`` (the linear layers' key head
    size, which the tree's shapes alone do not tell from the number of key
    heads), ``rope_theta`` and ``rotary_dim``."""
    x = params["embed"][tokens]
    load_balance, counts = 0.0, []
    for p in params["layers"]:
        x, lb, c = jax.checkpoint(lambda x, p: _layer(
            x, p, positions, norm_eps=norm_eps, **layer))(x, p)
        load_balance = load_balance + lb
        counts.append(c)
    x = _rmsnorm0(x, params["out_norm"], norm_eps)
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ev->bsv", x, params["lm_head"], precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    ce = -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)
    return ce + load_balance_coef * load_balance, {
        "cross_entropy": ce, "load_balance": load_balance,
        "counts": jnp.stack(counts)}


def loss_and_grad(params, tokens, targets, positions, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss, the mean of each part (tokens per expert summed), and the
    mean gradient."""
    fn = jax.jit(lambda p, *data: jax.value_and_grad(
        lambda q: shard_loss(q, *data, **model), has_aux=True)(p))
    n = len(tokens)
    parts: dict = {}

    def one(p, *data):
        (loss, aux), grad = fn(p, *data)
        for key, value in aux.items():
            scale = 1.0 if key == "counts" else 1.0 / n
            parts[key] = parts.get(key, 0.0) + scale * jax.device_get(value)
        return loss, grad

    loss, grad = shards.loss_and_grad(one, params, tokens, targets, positions)
    return loss, {k: v if k == "counts" else float(v)
                  for k, v in parts.items()}, grad
