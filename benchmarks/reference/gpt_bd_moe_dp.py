"""Plain float32 reference of the ``gpt_bd_moe_dp`` job: a grouped-query
decoder whose every feed-forward is an expert block (``model_type:
sdar_moe``, Qwen3-MoE's block), **trained by diffusion over blocks**
(SDAR, arXiv:2510.06303; the objective is BD3-LM's, arXiv:2503.09573): its
noising, its mask, its weighted loss, gradient and AdamW first step.

``jax.numpy`` alone, every product at ``highest`` precision, no kernel, no
sort and no grouped matmul.

Departures from "plain", so that the timed step's own sample (8,192 data
tokens, 16,384 rows) fits on the chip beside the parameters and their
gradient: each layer is wrapped in ``jax.checkpoint``; its attention makes
the logits of a block of query rows at a time (``LOGIT_ELEMENTS`` a head:
256 rows at 16,384 keys, 0.54 GB of float32 logits over 32 heads) and the
block's rows of the mask from the three clauses, the blocks a ``lax.map``
whose body is under a checkpoint of its own; its experts are applied one at
a time, each with its weight under a checkpoint, as a ``lax.scan`` (one body
to compile: the whole reference compiles in 46 s where a Python loop over
the 16 took 135, and holds 5.6 GiB of temporaries for 7.5); the head's
logits are made ``HEAD_ROWS`` rows at a time. The arithmetic is unchanged.

**A sample** is ``L`` data tokens ``x`` in ``L / B`` blocks of ``B``;
``blk(i) = i // B``. :func:`noised_batch`: for each block ``b`` draw ``t_b``
uniform in ``[eps, 1)``; each token of block ``b`` becomes the mask id with
probability ``t_b``, independently: ``x~``. The input is the ``2 L`` rows
``[x~ ; x]`` at positions ``[0..L-1 ; 0..L-1]``: row ``i < L`` is *noised*,
row ``L + i`` *clean*.

**The mask** over the ``2 L x 2 L`` pairs (:func:`block_diffusion_mask`), with
``bq = blk(q mod L)``, ``bk = blk(k mod L)``:

    keep(q, k) =  (q <  L and k <  L and bk == bq)   noised: its own block
               or (q <  L and k >= L and bk <  bq)   noised: the clean past
               or (q >= L and k >= L and bk <= bq)   clean: block-causal

**A layer**: ``h = x + Attn(N(x))``, ``y = h + MoE(N(h))``, ``N`` an RMSNorm
(weight as it is). ``q = W_q n`` as ``H`` heads, ``k``, ``v`` as ``H_kv``;
q and k through an RMSNorm over each head (one weight of ``head_dim`` for
all heads), then the rotary embedding on the whole head (two halves against
each other) at the positions above; scores ``q . k / sqrt(head_dim)`` under
``keep``, softmax, the values of the query head's group, ``W_o``.
``MoE(n)``: ``p = softmax(W_r n)`` over all the router's experts; the
``top_k`` largest; their weights divided by their sum; **every expert held
here is applied to every token** and the results are summed under those
weights (zero where an expert was not chosen); what the experts held
elsewhere would have added is left out. The load-balance term is ``E sum_e
f_e P_e`` a layer, over all ``2 L`` rows.

**The loss.** Logits at noised row ``i`` predict ``x_i`` in place, no shift.
With ``m_i = 1`` where ``x~_i`` is the mask id:

    loss = 1 / (batch L) * sum_{i < L, m_i = 1} (1 / t_blk(i)) (-log softmax(logits_i)[x_i])
           + load_balance_coef * sum over layers of the load-balance term

The head multiplies the ``L`` noised rows alone.

It reads the parameter tree ``models/gpt.py::init_params`` makes; parameters
are the interface, the arithmetic is its own. It imports nothing from the
program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference import shards
from benchmarks.reference.gpt_dp import adamw_first_update_norm  # noqa: F401

HI = lax.Precision.HIGHEST
# A block of query rows' logits a head, in elements: rows x keys.
LOGIT_ELEMENTS = 1 << 22
HEAD_ROWS = 1024


def noised_batch(rng, batch: int, length: int, block: int, eps: float,
                 mask_id: int):
    """One batch from ``rng`` (a ``numpy`` generator), drawn in this order:
    the data ids ``[batch, length]`` from ``[0, mask_id)``, one ``t`` a
    block uniform in ``[eps, 1)``, one uniform number a token (the token is
    masked where it is under its block's ``t``). Returns ``(tokens [batch,
    2 length], targets [batch, length], positions [batch, 2 length],
    weights [batch, length] float32)``: ``[x~ ; x]``; ``x`` where the token
    was masked and -1 elsewhere; ``[0..L-1 ; 0..L-1]``; ``1 / t`` of the
    token's block."""
    x = rng.integers(0, mask_id, (batch, length), dtype=np.int32)
    t = eps + (1.0 - eps) * rng.random((batch, length // block))
    draw = rng.random((batch, length))
    tokens = np.empty((batch, 2 * length), np.int32)
    targets = np.full((batch, length), -1, np.int32)
    weights = np.empty((batch, length), np.float32)
    for b in range(batch):
        for i in range(length):
            t_block = t[b, i // block]
            masked = draw[b, i] < t_block
            tokens[b, i] = mask_id if masked else x[b, i]
            tokens[b, length + i] = x[b, i]
            if masked:
                targets[b, i] = x[b, i]
            weights[b, i] = 1.0 / t_block
    positions = np.tile(np.arange(length, dtype=np.int32), (batch, 2))
    return tokens, targets, positions, weights


def _keep(q, k, length: int, block: int):
    """The three clauses on rows ``q`` ``[R, 1]`` and keys ``k`` ``[1, S]``
    of the ``2 L`` (integers, ``numpy``'s or traced)."""
    bq, bk = q % length // block, k % length // block
    return ((q < length) & (k < length) & (bk == bq)) \
        | ((q < length) & (k >= length) & (bk < bq)) \
        | ((q >= length) & (k >= length) & (bk <= bq))


def block_diffusion_mask(length: int, block: int) -> np.ndarray:
    """The ``[2 L, 2 L]`` boolean mask: the three clauses, in integers."""
    at = np.arange(2 * length)
    return _keep(at[:, None], at[None, :], length, block)


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _rows_attention(q, k, v, first, block: int):
    """Query rows ``first ..`` (a scalar array) of every head against all
    ``2 L`` keys under the block-diffusion mask: ``q`` ``[b, R, H, D]``,
    ``k`` and ``v`` ``[b, 2 L, H, D]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    keep = _keep(first + jnp.arange(q.shape[1])[:, None],
                 jnp.arange(k.shape[1])[None, :], k.shape[1] // 2, block)
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=HI)


def _attention_mixer(h, p, positions, *, block, rope_theta, norm_eps):
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"], precision=HI)
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"], precision=HI)
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"], precision=HI)
    q = _rope(_rmsnorm(q, p["q_norm"], norm_eps), positions, rope_theta)
    k = _rope(_rmsnorm(k, p["k_norm"], norm_eps), positions, rope_theta)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    b, seq = q.shape[:2]
    rows = min(seq, max(1, LOGIT_ELEMENTS // seq))
    if seq % rows:
        raise ValueError(f"{seq} keys are no whole number of blocks of "
                         f"{rows} query rows")
    some = jax.checkpoint(
        lambda rows_q, first: _rows_attention(rows_q, k, v, first, block))
    a = lax.map(lambda xs: some(*xs),
                (jnp.moveaxis(q.reshape(b, seq // rows, rows, *q.shape[2:]),
                              1, 0), jnp.arange(0, seq, rows)))
    a = jnp.moveaxis(a, 0, 1).reshape(q.shape)
    return jnp.einsum("bshd,hde->bse", a, p["wo"], precision=HI)


def router_logits(h, router):
    """What a router ``[d, E]`` gives on activations ``h`` ``[T, d]``: a
    float32 product at the highest precision, whatever ``h`` came as."""
    return jnp.dot(h.astype(jnp.float32), router, precision=HI)


def _expert(h, weight, w_gate, w_up, w_down):
    """One expert on every token under the tokens' weights for it ``[T]``
    (0 where it was not chosen)."""
    hidden = jax.nn.silu(jnp.dot(h, w_gate, precision=HI)) \
        * jnp.dot(h, w_up, precision=HI)
    return weight[:, None] * jnp.dot(hidden, w_down, precision=HI)


def expert_block(h, m, top_k: int, first_expert: int = 0):
    """``h`` ``[T, d]``, ``m`` the block's parameters -> ``(y [T, d],
    load-balance term, tokens per expert [E])``; ``y`` is the held experts'
    part of the sum."""
    experts, held = m["router"].shape[-1], m["w_up"].shape[0]
    probs = jax.nn.softmax(router_logits(h, m["router"]), axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, experts, dtype=h.dtype), axis=1)
    weights = chosen * probs / jnp.sum(top_p, axis=-1, keepdims=True)
    # One expert at a time, as a scan: one body to compile, not ``held``.
    y, _ = lax.scan(
        lambda y, one: (y + jax.checkpoint(_expert)(h, *one), None),
        jnp.zeros_like(h),
        (weights[:, first_expert:first_expert + held].T, m["w_gate"],
         m["w_up"], m["w_down"]))
    counts = jnp.sum(chosen, axis=0)
    load_balance = experts * jnp.sum(
        lax.stop_gradient(counts / h.shape[0]) * jnp.mean(probs, axis=0))
    return y, load_balance, counts


def _layer(x, p, positions, *, block, top_k, first_expert, rope_theta,
           norm_eps):
    x = x + _attention_mixer(_rmsnorm(x, p["attn_norm"], norm_eps), p,
                             positions, block=block, rope_theta=rope_theta,
                             norm_eps=norm_eps)
    h = _rmsnorm(x, p["mlp_norm"], norm_eps)
    y, load_balance, counts = expert_block(
        h.reshape(-1, h.shape[-1]), p["moe"], top_k, first_expert)
    return x + y.reshape(x.shape), load_balance, counts


def hidden(params, tokens, positions, *, norm_eps: float, **layer):
    """``(the stream after the last layer [b, 2 L, d], load-balance term,
    counts [layers, E])``; ``layer`` holds ``block``, ``top_k``,
    ``first_expert`` and ``rope_theta``."""
    x = params["embed"][tokens]
    load_balance, counts = 0.0, []
    for p in params["layers"]:
        x, lb, c = jax.checkpoint(lambda x, p: _layer(
            x, p, positions, norm_eps=norm_eps, **layer))(x, p)
        load_balance = load_balance + lb
        counts.append(c)
    return x, load_balance, jnp.stack(counts)


def noised_logits(params, tokens, positions, *, norm_eps: float, **model):
    """The logits of the noised rows ``[b, L, V]`` (the tests' no-leak
    cases read them)."""
    x, _, _ = hidden(params, tokens, positions, norm_eps=norm_eps, **model)
    x = _rmsnorm(x[:, :tokens.shape[1] // 2], params["out_norm"], norm_eps)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"], precision=HI)


def _rows_loss(x, targets, weights, head):
    """The weighted sum of the cross-entropy of rows ``x`` ``[R, d]``
    against ``targets`` ``[R]`` (-1: not masked, no term)."""
    logp = jax.nn.log_softmax(jnp.dot(x, head, precision=HI))
    masked = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(masked, targets, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(masked, weights * picked, 0.0))


def shard_loss(params, tokens, targets, positions, weights, *,
               load_balance_coef: float, **model):
    """``(loss, parts)``: ``parts`` holds ``cross_entropy`` (the weighted
    sum over the masked tokens, over the data tokens), ``load_balance`` (the
    sum over layers) and ``counts`` ``[layers, E]``. ``model`` holds
    ``block``, ``top_k``, ``first_expert``, ``rope_theta``, ``norm_eps``."""
    x, load_balance, counts = hidden(params, tokens, positions, **model)
    x = _rmsnorm(x[:, :targets.shape[1]], params["out_norm"],
                 model["norm_eps"]).reshape(-1, x.shape[-1])
    flat, by = targets.reshape(-1), weights.reshape(-1)
    ce = sum(
        jax.checkpoint(_rows_loss)(x[i:i + HEAD_ROWS], flat[i:i + HEAD_ROWS],
                                   by[i:i + HEAD_ROWS], params["lm_head"])
        for i in range(0, x.shape[0], HEAD_ROWS)) / targets.size
    return ce + load_balance_coef * load_balance, {
        "cross_entropy": ce, "load_balance": load_balance, "counts": counts}


@functools.lru_cache(maxsize=None)
def _value_and_grad(model: tuple):
    """One compiled program for a model's keywords: the check calls it
    twice on a sample's shapes."""
    return jax.jit(lambda p, *data: jax.value_and_grad(
        lambda q: shard_loss(q, *data, **dict(model)), has_aux=True)(p))


def loss_and_grad(params, tokens, targets, positions, weights, **model):
    """Arrays are ``[shards, b, .]``; ``model`` is ``shard_loss``'s
    keywords. The mean loss, the mean of each part (tokens per expert
    summed), and the mean gradient."""
    fn = _value_and_grad(tuple(sorted(model.items())))
    n = len(tokens)
    parts: dict = {}

    def one(p, *data):
        (loss, aux), grad = fn(p, *data)
        for key, value in aux.items():
            scale = 1.0 if key == "counts" else 1.0 / n
            parts[key] = parts.get(key, 0.0) + scale * jax.device_get(value)
        return loss, grad

    loss, grad = shards.loss_and_grad(one, params, tokens, targets,
                                      positions, weights)
    return loss, {k: v if k == "counts" else float(v)
                  for k, v in parts.items()}, grad


# What the check is tried against, planted on
# ``ops/flash_attention.py::Mask`` by ``benchmarks/tests/test_bd_faults.py``
# and ``scripts/check_sweep.py`` (``Mask.keep = own_clean_block_keep``):
# plain functions of the mask's two fields, here so that the test and the
# sweep plant one fault.

def own_clean_block_keep(mask, q_pos, k_pos, kv_len):
    """``Mask.keep`` with the second clause one block too wide, ``bk <=
    bq``: a noised row sees its **own** clean block, the token it is to
    predict among its keys. (The tiles the grids walk hold those pairs
    already: a noised block's own clean block lies in a tile that holds
    earlier clean blocks too, at any tile wider than a block.)"""
    shift = mask.block_diffusion.bit_length() - 1
    noised_q, noised_k = q_pos < mask.half, k_pos < mask.half
    bq = (q_pos - mask.half * (1 - noised_q)) >> shift
    bk = (k_pos - mask.half * (1 - noised_k)) >> shift
    return (noised_q & noised_k & (bk == bq)) \
        | (noised_q & ~noised_k & (bk <= bq)) \
        | (~noised_q & ~noised_k & (bk <= bq))


def tile_dropped(tile_kept):
    """``Mask.tile_kept`` less one tile of the grid: the last noised query
    tile's first clean key tile (of the noised-clean part, above the
    square's diagonal). The rows of one tile in sixteen lose an eighth of
    their keys, and every pair the kernels do compute is masked as it
    should be: a fault of the table, not of ``keep``. **The cell's check does
    not catch it on every seed** (the job's header has the readings);
    ``scripts/flash_block_sweep.py --dense-long sdar`` holds the kernels
    alone to dense attention at that grid."""
    def faulty(mask, qi, kj, block_q, block_k):
        if mask.block_diffusion is not None \
                and (qi + 1) * block_q == mask.half \
                and kj * block_k == mask.half:
            return False
        return tile_kept(mask, qi, kj, block_q, block_k)
    return faulty
