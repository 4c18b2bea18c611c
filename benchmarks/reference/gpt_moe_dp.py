"""Plain float32 reference of the ``gpt_moe_dp`` job: OLMoE's forward pass,
loss with both auxiliary terms, gradient and AdamW first step.

A pre-norm decoder in ``jax.numpy`` alone, every product at ``highest``
precision, no kernel, no recomputation, no sort and no grouped matmul, after
the published model (arXiv:2409.02060; ``modeling_olmoe.py``): RMSNorm ->
q, k, v -> RMSNorm of the whole query and of the whole key projection (all
heads together) -> rotary embedding -> causal soft-max attention -> output
projection -> residual; RMSNorm -> expert layer -> residual; final RMSNorm,
untied head, mean next-token cross-entropy over the targets that are not -1.

The expert layer, for tokens ``h``: ``p = softmax(h W_r)``; ``S_t`` the ``k``
largest of ``p_t``; **every expert is applied to every token** and the
results are summed under the weights ``p_{t,e}`` for ``e`` in ``S_t`` and 0
elsewhere, not renormalised (``norm_topk_prob: false``). The loss adds
``load_balance_coef`` times the sum over layers of ``E sum_e f_e P_e``
(``f_e`` the share of tokens whose ``S_t`` holds ``e``, ``P_e`` the mean of
``p_{t,e}``) and ``router_z_coef`` times the sum over layers of the mean
squared log-sum-exp of the router's logits.

The rotary embedding rotates the two halves of a head (``rotate_half``, the
published model's convention), which is what ``models/transformer.py::rope``
computes. It reads the parameter tree ``models/gpt.py::init_params`` makes;
parameters are the interface, the arithmetic is its own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards

HI = lax.Precision.HIGHEST
ROPE_BASE = 10000.0


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions):
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def expert_layer(h, router, w_gate, w_up, w_down, top_k: int):
    """``h`` ``[T, d]`` -> ``(y [T, d], load-balance term, z term, tokens per
    expert [E])``."""
    experts = router.shape[-1]
    logits = jnp.dot(h, router, precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, experts, dtype=h.dtype), axis=1)
    weights = chosen * probs                                    # [T, E]
    gate = jnp.einsum("td,edm->etm", h, w_gate, precision=HI)
    up = jnp.einsum("td,edm->etm", h, w_up, precision=HI)
    out = jnp.einsum("etm,emd->etd", jax.nn.silu(gate) * up, w_down,
                     precision=HI)
    y = jnp.einsum("te,etd->td", weights, out, precision=HI)
    counts = jnp.sum(chosen, axis=0)
    load_balance = experts * jnp.sum(
        lax.stop_gradient(counts / h.shape[0]) * jnp.mean(probs, axis=0))
    router_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, load_balance, router_z, counts


def _layer(x, p, positions, top_k, eps):
    h = _rmsnorm(x, p["attn_norm"], eps)
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)

    def whole(t, w):        # the norm is over heads x head_dim as one vector
        flat = t.reshape(t.shape[:2] + (-1,))
        return _rmsnorm(flat, w.reshape(-1), eps).reshape(t.shape)

    q = _rope(whole(q, p["q_norm"]), positions)
    k = _rope(whole(k, p["k_norm"]), positions)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    x = x + jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI)
    h = _rmsnorm(x, p["mlp_norm"], eps)
    m = p["moe"]
    y, load_balance, router_z, counts = expert_layer(
        h.reshape(-1, h.shape[-1]), m["router"], m["w_gate"], m["w_up"],
        m["w_down"], top_k)
    return x + y.reshape(x.shape), load_balance, router_z, counts


def shard_loss(params, tokens, targets, positions, *, top_k: int,
               norm_eps: float, load_balance_coef: float,
               router_z_coef: float):
    """``(loss, parts)``: ``parts`` holds ``cross_entropy``, ``load_balance``
    and ``router_z`` (sums over layers) and ``counts`` ``[layers, E]``."""
    x = params["embed"][tokens]
    load_balance = router_z = 0.0
    counts = []
    for p in params["layers"]:
        x, lb, rz, c = _layer(x, p, positions, top_k, norm_eps)
        load_balance, router_z = load_balance + lb, router_z + rz
        counts.append(c)
    x = _rmsnorm(x, params["out_norm"], norm_eps)
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ev->bsv", x, params["lm_head"], precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    ce = -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)
    loss = ce + load_balance_coef * load_balance + router_z_coef * router_z
    return loss, {"cross_entropy": ce, "load_balance": load_balance,
                  "router_z": router_z, "counts": jnp.stack(counts)}


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


def adamw_first_update_norm(params, grad, lr, weight_decay, eps) -> float:
    """The norm of what AdamW's first step adds to the parameters. With both
    moments at zero the bias-corrected ones are ``g`` and ``g * g``, so the
    step is ``-lr * (g / (|g| + eps) + weight_decay * p)`` (Loshchilov and
    Hutter, arXiv:1711.05101, algorithm 2)."""
    step = jax.jit(lambda p, g: jnp.sum(jnp.square(
        lr * (g / (jnp.abs(g) + eps) + weight_decay * p))))
    return sum(float(step(p, g)) for p, g in zip(
        jax.tree.leaves(params), jax.tree.leaves(grad))) ** 0.5
