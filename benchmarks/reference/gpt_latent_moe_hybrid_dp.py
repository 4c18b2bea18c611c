"""Plain float32 reference of the ``gpt_latent_moe_hybrid_dp`` job: a decoder
whose blocks are one sublayer each (a Mamba-2 mixer, a grouped-query
attention mixer or an expert feed-forward, by a pattern of ``M``, ``*`` and
``E``), the experts un-gated squared-ReLU ones in a latent narrower than the
stream beside a shared expert on the stream (``model_type: nemotron_h``,
NVIDIA-Nemotron-3-Super-120B-A12B), its loss, gradient, AdamW first step and
the selection bias's update.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
chunked scan, no sort and no grouped matmul: the state-space recurrence one
token a step, ``S x S`` logits under the causal mask, every held expert
applied to every token. Written from the published configuration's keys;
what is no key of it is from memory of transformers'
``modeling_nemotron_h.py`` and of arXiv:2504.03624 (there is no network
here) and is listed under ``assumed`` in the configuration file, (a) below.
The equations, ``N`` an RMSNorm with a plain weight::

    RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w
    block i of kind c:  x = x + f_c(N_i(x))        one norm, one sublayer (a)
    logits = W_head RMSNorm(x_L)                   untied head; no bias
                                                   anywhere but the conv's

    M, Mamba-2 (H heads of P, state N, G groups, K taps):
        [z | xBC | dt] = h W_in           inner | inner + 2 G N | H
        xBC = silu(conv(xBC)): causal, depthwise, K taps and a bias,
            conv(u)_t = b + sum_k w_k u_{t-(K-1)+k}, zeros before the start
        [x | B | C] = split(xBC)                    inner | G N | G N
        dt = softplus(dt + dt_bias);  A = -exp(A_log)      one a head
        head h of group h // (H / G), state S in R^{P x N}, S_0 = 0:
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        y = RMSNorm_g(y * silu(z)) * w: **the mean square over each
            group's inner / G channels** (a)
        out = W_out y
    *, attention: q, k, v = W_q h, W_k h, W_v h; causal softmax(q k^T /
        sqrt(D)) v over Hq query heads on Hkv key/value heads (query head j
        reads key/value head j // (Hq / Hkv)); **no rotary embedding** (a);
        W_o
    E, LatentMoE (router E_r wide, k a token, latent L, expert width m):
        s = sigmoid(h W_r)                          float32
        S_t = the k largest of s_t + b              b [E_r], no gradient, in
                                                    the choice alone;
                                                    n_group = topk_group = 1
        w_te = routed_scaling_factor * s_te / (sum_{e' in S_t} s_te' + 1e-20)
        u = h W_down_latent                         E -> L (a)
        y = (sum_{e in S_t, e held} w_te W2_e relu(W1_e u)^2) W_up_latent
            + W2_s relu(W1_s h)^2                   the shared expert on the
                                                    stream, no gate (a)
    **This chip's share**: the tree holds experts ``first_expert`` to
    ``first_expert + held`` of E_r (``held`` is the expert matrices' first
    axis), a share of the Mamba heads with their groups, of the attention
    heads and of the vocabulary, each a smaller model whose parameters are
    slices of the whole's; the router, the bias, the choice and the
    renormalisation are over all E_r, the sum over the held experts alone,
    and that partial sum goes on to the next block. Nothing stands in for
    what is elsewhere.
    loss: mean next-token cross-entropy over the vocabulary held; no
    auxiliary term (a).
    after the optimizer's step (a): with c_e the tokens expert e of a block
    got in that step over all data-parallel ranks,
        d_e = rate * sign(mean(c) - c_e);   b <- b + d - mean(d)
    AdamW neither moves nor decays b.

Departures from "plain": each block is wrapped in ``jax.checkpoint``; the
recurrence is a ``lax.scan`` over time whose every ``scan_block`` tokens are
a checkpoint of their own, so that the per-token states a backward pass
holds (0.5 MB a token at 16 heads of 64 x 128) are one block's and not the
sequence's (4.3 GB at 8192 tokens); attention runs over blocks of query
rows, each under a checkpoint of its own. The arithmetic is unchanged.

It reads the parameter tree ``models/gpt.py::init_params`` makes (a block
with an ``ssm`` entry is a Mamba-2 block, one with ``wq`` an attention
block, one with ``moe`` an expert block; the block's one norm is
``ssm_norm``, ``attn_norm`` or ``mlp_norm``); parameters are the interface,
the arithmetic is its own. The selection biases' update and AdamW's first
step are those of the ``gpt_window_moe_dp`` reference, whose equations they
share term for term. It imports nothing from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_window_moe_dp import (  # noqa: F401
    adamw_first_update_norm, bias_step_on_load, biases, router_logits,
    updated_biases)

HI = lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def recurrence(x, dt, a, b_in, c_in, d, scan_block: int = 128):
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

    @jax.checkpoint
    def tokens(state, some):
        return lax.scan(step, state, some)

    seq = x.shape[1]
    block = scan_block if seq % scan_block == 0 else seq
    # [blocks, block, b, ...]: time in front, a block of it at a time.
    over_time = tuple(
        jnp.moveaxis(t, 1, 0).reshape(seq // block, block, *t.shape[:1],
                                      *t.shape[2:])
        for t in (x, dt, b_in, c_in))
    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b_in.shape[-1:], x.dtype)
    _, y = lax.scan(tokens, start, over_time)
    return jnp.moveaxis(y.reshape(seq, *y.shape[2:]), 0, 1)


def mamba_mixer(h, p, state: int, eps: float):
    """A Mamba-2 mixer ``p`` on normed activations ``h`` ``[b, S, E]``; the
    sizes are read off the parameters, the groups from the convolved
    channels beyond the inner width (``2 G N``, ``N = state``)."""
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
    gated = (y.reshape(batch, seq, inner) * jax.nn.silu(z)).reshape(
        batch, seq, groups, inner // groups)
    y = _rmsnorm(gated, p["norm"].reshape(groups, inner // groups), eps)
    return jnp.einsum("bsf,fe->bse", y.reshape(batch, seq, inner),
                      p["out_proj"], precision=HI)


def _rows_attention(q, k, v, first):
    """Query rows ``first ..`` of every head against all keys: ``q`` ``[b,
    R, H, D]``, ``k`` and ``v`` ``[b, S, H, D]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    i = first + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(i >= jnp.arange(k.shape[1])[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=HI)


def attention_mixer(h, p, query_rows: int = 1024):
    """Causal grouped-query attention without a position embedding."""
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    seq = q.shape[1]
    rows = min(query_rows, seq)
    a = jnp.concatenate([
        jax.checkpoint(_rows_attention, static_argnums=3)(
            q[:, i:i + rows], k, v, i)
        for i in range(0, seq, rows)], axis=1)
    return jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI)


def relu2_expert(h, w_up, w_down):
    """``W_down relu(W_up h)^2``: two matrices and no gate."""
    return jnp.dot(jnp.square(jax.nn.relu(jnp.dot(h, w_up, precision=HI))),
                   w_down, precision=HI)


def routing(h, m, top_k: int, route_scale: float):
    """``(weights [T, E_r], chosen [T, E_r])``: a token's weights on all the
    router's experts (nothing on those it did not choose) and the choice as
    ones and zeros."""
    experts = m["router"].shape[-1]
    scores = jax.nn.sigmoid(router_logits(h, m["router"]))
    _, top_e = lax.top_k(scores + lax.stop_gradient(m["router_bias"]),
                         top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, experts, dtype=h.dtype), axis=1)
    weights = route_scale * chosen * scores / (
        jnp.sum(chosen * scores, axis=-1, keepdims=True) + 1e-20)
    return weights, chosen


def routed_latent(h, m, top_k: int, route_scale: float,
                  first_expert: int = 0):
    """``(the held experts' weighted sum in the latent [T, L], tokens per
    expert [E_r])`` for ``h`` ``[T, E]``: before the up-projection, which is
    linear, so that the shares' sums add up in the latent."""
    held = m["w_up"].shape[0]
    weights, chosen = routing(h, m, top_k, route_scale)
    weights = weights[:, first_expert:first_expert + held]      # [T, held]
    u = jnp.dot(h, m["latent_down"], precision=HI)
    hidden = jnp.square(jax.nn.relu(
        jnp.einsum("tl,elm->etm", u, m["w_up"], precision=HI)))
    out = jnp.einsum("etm,eml->etl", hidden, m["w_down"], precision=HI)
    return jnp.einsum("te,etl->tl", weights, out, precision=HI), \
        jnp.sum(chosen, axis=0)


def expert_block(h, m, top_k: int, route_scale: float,
                 first_expert: int = 0):
    """``h`` ``[T, E]``, ``m`` the block's parameters -> ``(y [T, E], tokens
    per expert [E_r])``; ``y`` is the held experts' part of the sum through
    the up-projection plus the shared expert."""
    latent, counts = routed_latent(h, m, top_k, route_scale, first_expert)
    s = m["shared"]
    return jnp.dot(latent, m["latent_up"], precision=HI) \
        + relu2_expert(h, s["w_up"], s["w_down"]), counts


def _block(x, p, *, top_k, route_scale, first_expert, ssm_state, norm_eps):
    if "ssm" in p:
        h = _rmsnorm(x, p["ssm_norm"], norm_eps)
        return x + mamba_mixer(h, p["ssm"], ssm_state, norm_eps), None
    if "wq" in p:
        h = _rmsnorm(x, p["attn_norm"], norm_eps)
        return x + attention_mixer(h, p), None
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    y, counts = expert_block(h.reshape(-1, h.shape[-1]), p["moe"], top_k,
                             route_scale, first_expert)
    return x + y.reshape(x.shape), counts


def shard_loss(params, tokens, targets, *, norm_eps: float, **block):
    """``(loss, parts)``: ``parts`` holds ``counts`` ``[expert blocks,
    E_r]``. ``block`` holds ``top_k``, ``route_scale``, ``first_expert`` and
    ``ssm_state`` (the state's width N, which the tree's shapes alone do not
    tell from the number of groups)."""
    x = params["embed"][tokens]
    counts = []
    for p in params["layers"]:
        x, c = jax.checkpoint(
            lambda x, p: _block(x, p, norm_eps=norm_eps, **block))(x, p)
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


def loss_and_grad(params, tokens, targets, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss, tokens per expert summed over the shards ``[expert
    blocks, E_r]``, and the mean gradient (the biases' is zero: they are in
    the choice alone)."""
    fn = jax.jit(lambda p, *data: jax.value_and_grad(
        lambda q: shard_loss(q, *data, **model), has_aux=True)(p))
    counts = []

    def one(p, *data):
        (loss, aux), grad = fn(p, *data)
        counts.append(jax.device_get(aux["counts"]))
        return loss, grad

    loss, grad = shards.loss_and_grad(one, params, tokens, targets)
    return loss, sum(counts), grad
