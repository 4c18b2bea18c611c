"""Plain float32 reference of the ``gpt_sambay_dp`` job: a
decoder-hybrid-decoder stack (SambaY; Ren et al., arXiv:2507.06607,
``Phi-4-mini-flash-reasoning``): Mamba-1 selective scans and differential
attention under a 512-key window in the first half, one Mamba-1 layer and one
full differential attention layer in the middle that **publish** their scan
output and their keys and values, and a second half that computes no memory
of its own: Gated Memory Units on the published scan output and differential
cross-attention on the published keys and values. Its loss, gradient and
AdamW first step.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel: the
selective scan is a ``lax.scan`` over tokens, one token a step; attention is
``S x S`` logits under the causal mask or the band, a block of query rows at
a time; the published values are passed from layer to layer by hand. Written
from the published configuration's keys (``model_type: phi4flash``) and from
memory of ``modeling_phi4flash.py``, of arXiv:2507.06607, of Mamba
(arXiv:2312.00752) and of the Differential Transformer (arXiv:2410.05258);
there is no network here, and what is no key of the config is listed under
``assumed`` in the configuration file. ``x`` the stream, ``LN`` a LayerNorm
with weight and bias::

    x_0 = E[tokens]                              no multiplier, no position
    layer l:  h = x + mixer_l(LN(x))
              x <- h + W_d (silu(u W_g) * (u W_u)),  u = LN(h)
    logits = E^T LN(x_L)                         tied head, no bias

    which mixer (N layers, mb_per_layer 2; ``published_layers``):
        l < N/2:      even l Mamba-1, odd l differential attention, window W
        l = N/2:      Mamba-1, which also publishes its scan output m
        l = N/2 + 1:  full causal differential attention, which also
                      publishes its K1, K2, V
        l >= N/2 + 2: even l a Gated Memory Unit on m, odd l differential
                      cross-attention on K1, K2, V

    Mamba-1:  [u | z] = h W_in;  u <- silu(conv(u) + b)  (depthwise, causal,
                  conv(u)_t = sum_k w_k u_{t-(K-1)+k}, zeros before the start)
              [r | B | C] = u W_x;  dt = softplus(r W_dt + b_dt)
              A = -exp(A_log)  [C, N]
              s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n]
                          + dt_t[c] B_t[n] u_t[c]
              y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]
              m = y (before the gate);  out = (y * silu(z)) W_out
    GMU:      out = (silu(h W_1) * m) W_2
    differential attention (H query and Hkv key/value heads of D, taken as
    pairs: heads 2j and 2j + 1; a pair's value heads side by side, 2 D wide;
    query pair j reads key/value pair j // (H / Hkv)):
              A_i = softmax(q_i k_i^T / sqrt(D))  under the causal mask, and
                    under the window 0 <= i - j < W where the layer has one
              o = A_1 v - lam A_2 v
              lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
              lam_init = 0.8 - 0.6 exp(-0.3 l),  l the PUBLISHED layer index
              out = (RMSNorm_2D(o) g (1 - lam_init)) W_o
              a cross layer has W_q, W_o, its own lq*, lk*, g and no key or
              value projection: k_1, k_2, v are layer N/2 + 1's

Departures from "plain", so that the job's whole 16,384-token sequence fits
on the chip beside the parameters and their gradient: each layer is wrapped
in ``jax.checkpoint``; attention makes the logits of a block of query rows
at a time (``LOGIT_ELEMENTS`` a head), the blocks a ``lax.map`` whose body is
under a checkpoint of its own; the scan runs in checkpointed blocks of
``SCAN_BLOCK`` tokens (its states ``[S, C, N]`` would be 5.4 GB a layer);
the head's logits are made ``HEAD_ROWS`` rows at a time. The arithmetic is
unchanged.

It reads the parameter tree ``models/gpt.py::init_params`` makes and is told
each layer's kind by the job, from the published rule below; parameters are
the interface, the arithmetic is its own. It imports nothing from the
program.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_dp import adamw_first_update_norm  # noqa: F401

HI = lax.Precision.HIGHEST
LOGIT_ELEMENTS = 1 << 22
SCAN_BLOCK = 128
HEAD_ROWS = 512
KINDS = ("mamba", "window", "full", "gmu", "cross")


class Layer(NamedTuple):
    """One layer of the published model: its index there, its kind (one of
    ``KINDS``), its window (None: every key before the query) and whether it
    publishes (a ``mamba`` layer its scan output, a ``full`` one its keys
    and values)."""
    depth: int
    kind: str
    window: Optional[int]
    publishes: bool


def published_layers(num_hidden_layers: int, mb_per_layer: int,
                     sliding_window: int) -> tuple:
    """The published model's layers, one :class:`Layer` each."""
    if mb_per_layer != 2 or num_hidden_layers % 4:
        raise ValueError("the rule is written for mb_per_layer 2 and a "
                         "depth that is a multiple of four")
    half = num_hidden_layers // 2
    layers = []
    for depth in range(num_hidden_layers):
        if depth % 2 == 0:
            kind = "mamba" if depth <= half else "gmu"
        else:
            kind = "window" if depth < half else \
                "full" if depth == half + 1 else "cross"
        layers.append(Layer(
            depth, kind, sliding_window if kind == "window" else None,
            depth in (half, half + 1)))
    return tuple(layers)


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _layernorm(x, p, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["weight"] + p["bias"]


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def selective_scan(u, dt, a, b_in, c_in, d):
    """The recurrence one token a step, in checkpointed blocks of
    ``SCAN_BLOCK`` tokens. ``u``, ``dt`` ``[b, S, C]``, ``a`` ``[C, N]``,
    ``b_in``, ``c_in`` ``[b, S, N]``, ``d`` ``[C]`` -> ``y`` ``[b, S, C]``."""
    batch, seq, channels = u.shape
    block = math.gcd(seq, SCAN_BLOCK)

    def step(state, now):
        u_t, dt_t, b_t, c_t = now
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d * u_t

    def by_block(t):
        """``[b, S, .]`` -> ``[S / block, block, b, .]``."""
        return jnp.moveaxis(t, 1, 0).reshape(
            (seq // block, block) + t.shape[:1] + t.shape[2:])

    start = jnp.zeros((batch,) + a.shape, u.dtype)
    _, y = lax.scan(jax.checkpoint(lambda s, xs: lax.scan(step, s, xs)),
                    start, tuple(by_block(t) for t in (u, dt, b_in, c_in)))
    return jnp.moveaxis(y.reshape(seq, batch, channels), 0, 1)


def mamba(h, p):
    """``(the mixer's output, the scan's output y before the gate)``."""
    inner, seq = p["D"].shape[0], h.shape[1]
    state = p["A_log"].shape[1]
    rank = p["dt_proj"].shape[0]
    uz = jnp.einsum("bse,ef->bsf", h, p["in_proj"], precision=HI)
    u, z = uz[..., :inner], uz[..., inner:]
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + seq] * p["conv_w"][k] for k in range(taps)))
    r, b_in, c_in = jnp.split(
        jnp.einsum("bsc,cf->bsf", u, p["x_proj"], precision=HI),
        [rank, rank + state], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", r, p["dt_proj"], precision=HI)
        + p["dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(p["A_log"]), b_in, c_in, p["D"])
    return jnp.einsum("bsc,ce->bse", y * jax.nn.silu(z), p["out_proj"],
                      precision=HI), y


def gmu(h, p, memory):
    gate = jnp.einsum("bse,ec->bsc", h, p["in_proj"], precision=HI)
    return jnp.einsum("bsc,ce->bse", jax.nn.silu(gate) * memory,
                      p["out_proj"], precision=HI)


def _rows_attention(q, k, v, first, window):
    """Query rows ``first ..`` (a scalar array) of every head against all
    keys under the causal mask or the band: ``q`` ``[b, R, H, D]``, ``k``
    ``[b, S, H, D]``, ``v`` ``[b, S, H, Dv]``."""
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


def softmax_attention(q, k, v, window):
    """``softmax(q k^T / sqrt(D)) v`` under the mask, heads already equal in
    number, a block of query rows at a time."""
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
    return jnp.moveaxis(a, 0, 1).reshape(q.shape[:3] + v.shape[3:])


def keys_and_values(h, p):
    """A layer's own ``(k_1, k_2, v)``: the first and the second key head of
    each pair ``[b, S, Hkv / 2, D]``, a pair's value heads side by side
    ``[b, S, Hkv / 2, 2 D]``."""
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    return k[:, :, 0::2], k[:, :, 1::2], jnp.concatenate(
        [v[:, :, 0::2], v[:, :, 1::2]], axis=-1)


def differential_attention(h, p, kv, *, depth: int, window, eps: float):
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    group = q1.shape[2] // kv[0].shape[2]
    k1, k2, v = (jnp.repeat(t, group, axis=2) for t in kv)
    a1 = softmax_attention(q1, k1, v, window)
    a2 = softmax_attention(q2, k2, v, window)
    init = lambda_init(depth)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
    o = _rmsnorm(a1 - lam * a2, p["subln"], eps) * (1.0 - init)
    return jnp.einsum("bshd,hde->bse", o, p["wo"], precision=HI)


def _layer(x, p, shared, layer: Layer, eps: float):
    """``(the stream after the layer, what it publishes or None)``; ``shared``
    is what the layer reads: the published scan output for a ``gmu`` layer,
    the published keys and values for a ``cross`` one."""
    published = None
    if layer.kind == "mamba":
        mixed, y = mamba(_layernorm(x, p["s6_norm"], eps), p["s6"])
        published = y if layer.publishes else None
    elif layer.kind == "gmu":
        mixed = gmu(_layernorm(x, p["gmu_norm"], eps), p["gmu"], shared)
    else:
        h = _layernorm(x, p["attn_norm"], eps)
        kv = shared if layer.kind == "cross" else keys_and_values(h, p)
        mixed = differential_attention(h, p, kv, depth=layer.depth,
                                       window=layer.window, eps=eps)
        published = kv if layer.publishes else None
    x = x + mixed
    u = _layernorm(x, p["mlp_norm"], eps)
    gate = jnp.einsum("bse,em->bsm", u, p["w_gate"], precision=HI)
    up = jnp.einsum("bse,em->bsm", u, p["w_up"], precision=HI)
    return x + jnp.einsum("bsm,me->bse", jax.nn.silu(gate) * up, p["w_down"],
                          precision=HI), published


def _rows_loss(x, targets, embed):
    """Summed cross-entropy of rows ``x`` ``[R, d]`` against ``targets``
    ``[R]`` (-1: none) under the tied head."""
    logp = jax.nn.log_softmax(jnp.einsum("re,ve->rv", x, embed,
                                         precision=HI))
    keep = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, targets, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0))


def shard_loss(params, tokens, targets, *, layers: tuple, norm_eps: float,
               vocab: Optional[int] = None):
    """Mean next-token cross-entropy over the targets that are not -1.
    ``layers`` holds one :class:`Layer` a layer of ``params``; ``vocab``:
    the loss is over the embedding's first ``vocab`` rows alone (None: all),
    the other rows' logits left out."""
    x = params["embed"][tokens]
    memory = kv = None
    for p, layer in zip(params["layers"], layers, strict=True):
        shared = {"gmu": memory, "cross": kv}.get(layer.kind)
        x, published = jax.checkpoint(
            lambda x, p, shared, layer=layer: _layer(
                x, p, shared, layer, norm_eps))(x, p, shared)
        if layer.publishes:
            if layer.kind == "mamba":
                memory = published
            else:
                kv = published
    x = _layernorm(x, params["out_norm"], norm_eps).reshape(-1, x.shape[-1])
    flat = targets.reshape(-1)
    embed = params["embed"][:vocab]
    total = sum(
        jax.checkpoint(_rows_loss)(x[i:i + HEAD_ROWS], flat[i:i + HEAD_ROWS],
                                   embed)
        for i in range(0, x.shape[0], HEAD_ROWS))
    return total / jnp.sum(flat != -1)


def loss_and_grad(params, tokens, targets, **model):
    """Arrays are ``[shards, b, S]``; ``model`` is ``shard_loss``'s keywords.
    The mean loss and the mean gradient."""
    fn = jax.jit(jax.value_and_grad(
        lambda p, *data: shard_loss(p, *data, **model)))
    return shards.loss_and_grad(fn, params, tokens, targets)
