"""Plain float32 reference of the ``gpt_dp`` job: loss, gradient, first update.

A pre-norm decoder in ``jax.numpy`` alone, every product at ``highest``
precision, no kernel, no recomputation, key and value heads repeated
explicitly: RMSNorm (eps 1e-6) -> q, k, v -> rotary embedding (base 10000,
halves rotated) -> causal soft-max attention -> output projection ->
residual; RMSNorm -> up -> tanh-GELU -> down -> residual; final RMSNorm,
untied head, mean next-token cross-entropy over the targets that are not
-1. It reads the parameter tree ``models/gpt.py::init_params`` makes;
parameters are the interface, the arithmetic is its own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards

HI = lax.Precision.HIGHEST
NORM_EPS = 1e-6
ROPE_BASE = 10000.0


def _rmsnorm(x, w):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + NORM_EPS) * w


def _rope(x, positions):
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, p, positions):
    h = _rmsnorm(x, p["attn_norm"])
    q = _rope(jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI),
              positions)
    k = _rope(jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI),
              positions)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
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
    h = _rmsnorm(x, p["mlp_norm"])
    up = _gelu_tanh(jnp.einsum("bse,em->bsm", h, p["w_up"], precision=HI))
    return x + jnp.einsum("bsm,me->bse", up, p["w_down"], precision=HI)


def shard_loss(params, tokens, targets, positions):
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = _layer(x, p, positions)
    x = _rmsnorm(x, params["out_norm"])
    logp = jax.nn.log_softmax(
        jnp.einsum("bse,ev->bsv", x, params["lm_head"], precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)


def loss_and_grad(params, tokens, targets, positions):
    """Arrays are ``[shards, b, S]``. The mean loss and the mean gradient."""
    return shards.loss_and_grad(jax.jit(jax.value_and_grad(shard_loss)),
                                params, tokens, targets, positions)


def adamw_first_update_norm(params, grad, lr, weight_decay, eps) -> float:
    """The norm of what AdamW's first step adds to the parameters. With both
    moments at zero the bias-corrected ones are ``g`` and ``g * g``, so the
    step is ``-lr * (g / (|g| + eps) + weight_decay * p)`` (Loshchilov and
    Hutter, arXiv:1711.05101, algorithm 2)."""
    step = jax.jit(lambda p, g: jnp.sum(jnp.square(
        lr * (g / (jnp.abs(g) + eps) + weight_decay * p))))
    return sum(float(step(p, g)) for p, g in zip(
        jax.tree.leaves(params), jax.tree.leaves(grad))) ** 0.5
