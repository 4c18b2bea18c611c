#!/usr/bin/env python3
"""Time the gated delta rule's kernels alone on the chip, and hold the
compiled kernels to the ``jax.numpy`` expressions they replaced.

    chiprun --chips 1 -- python scripts/gdn_kernel_time.py [--substitute 1 8 16 64] [--chunks-per-block 2 4 8]
        [--rec-heads 4 8] [--rec-chunks 1 2 4] [--rows chunk_local recurrence whole] [--caller-norms]

At the ``qwen3-next-80b-a3b_s4096`` cell's shapes (4 x 4096 tokens, 16 key and
32 value heads of 128, chunk 64, bfloat16; ``--batch 1 --seq 8192 --key-heads
30 --value-heads 30 --key-dim 96 --value-dim 192`` are the
``olmo-hybrid-7b_s8192`` cell's: the kernels alone are then given what
``gated_delta_chunked`` gives them, heads padded with zeros to whole lane
tiles, 128 by 256, and the ``whole`` row the published sizes) it jits and
times, host clock
around ``block_until_ready``: ``hvd_gdn_fwd``; ``hvd_gdn_bwd``, handed the
float32 ``T`` that forward call wrote (since PR 68 it makes no inverse); the
chunk-local part forward and backward through the ``custom_vjp``; the same through the
plain expression (XLA writes the ``[chunk, chunk]`` tensors to HBM, the
inverse is ``unit_lower_inverse``); and ``gated_delta_chunked`` whole, forward
and backward. Since PR 50 the chunk-local kernels norm the rows of ``q`` and
``k`` themselves (``norm_qk``): they are given raw rows, as a mixer's
convolution leaves them, the plain expression norms them in its own lines
(``unit_rows``, rounded to bfloat16 before the products as the kernels
round), and the gradients compared are the raw rows'; ``--caller-norms``
times the kernels on normed rows instead, as they ran before. Then the recurrence over chunks on the forward kernel's
outputs: the ``lax.scan`` ``gated_delta_chunked`` ran before PR 36 (kept
here, :func:`scan_recurrence`), forward and with autodiff's backward, against
``hvd_gdn_rec_fwd`` (as the forward pass runs it and as the rule's forward
does, entering states kept) and ``hvd_gdn_rec_bwd``, alone and through the
``custom_vjp``; ``--rec-heads`` and ``--rec-chunks`` force the value heads and
the chunks a grid cell holds (the sources of ``_REC_HEADS``, ``_REC_CHUNKS``).
The kernels' values and gradients are compared with the plain expressions'
(relative to the largest value). ``--substitute`` forces the rows of the
inverse's diagonal blocks made by substitution (1: every round a product;
the chunk: no product; the forward kernel's alone since PR 68, the backward
row no longer moves with it) and ``--chunks-per-block`` the chunks a grid cell
walks: the sources of ``ops/gated_delta.py::_SUBSTITUTE`` and
``_MAX_CHUNKS``. One JSON line a row, also appended to
``chiprun_out/gdn_kernel_time.jsonl``.

**Summing a step from these rows.** A layer of a checkpointed step
(``remat="full"``) runs ``hvd_gdn_fwd`` once, ``hvd_gdn_rec_fwd`` once where
the heads are whole lane tiles (the Qwen cell's) and twice where they are
carried padded (the Olmo cell's: the recomputed copy makes the entering
states again), ``hvd_gdn_bwd`` once and ``hvd_gdn_rec_bwd`` once: since
PR 56 the block keeps what the first kernel writes and, unpadded, the
entering states (``gdn_scan_operands``, ``gdn_scan_entering``). Before it
both forward kernels ran twice a layer. The forward pass runs the
recurrence's kernel as the rule's forward does (``fwd_keep_ms``). Since
PR 68 what the first kernel writes includes ``T`` (134 MB a layer at the
default shape, 63 at the Olmo cell's, kept with the five operands), the
inverse is made once a layer a step, in ``fwd_ms``, and ``bwd_ms`` holds
none: 11.99 -> 7.22 ms at the default shape, 10.17 -> 6.49 at the Olmo
cell's (PERF.md, Findings, PR 68).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.ops import gated_delta as gd  # noqa: E402
from horovod_tpu.ops.pallas_util import to_lanes  # noqa: E402


def timed(fn, *args, reps: int = 10) -> float:
    """ms a call: the mean of ``reps`` calls after two warm ones."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def plain_chunk_local(q, k, v, cum, beta, q_scale=None):
    """What ``gated_delta_chunked`` computed before the kernels, on their
    arguments (``ops/gated_delta.py::_fwd_call``) and in their outputs'
    order: the rows' norms where the kernels make them (``q_scale``),
    ``[B, c, H, Q, Q]`` float32 decays, ``K K^T``, ``A`` and the inverse's
    rounds through HBM, autodiff's backward."""
    f32, dtype = jnp.float32, q.dtype
    if q_scale is not None:
        q = gd.unit_rows(q, q_scale).astype(dtype)
        k = gd.unit_rows(k).astype(dtype)
    batch, n_chunks, chunk, heads = cum.shape
    rep = heads // q.shape[2]

    def chunked(t):
        t = t.reshape((batch, n_chunks, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 2, 3)

    def by_value_head(t):
        return jnp.repeat(t, rep, axis=2) if rep > 1 else t

    q, k, v = chunked(q), chunked(k), chunked(v)
    cum, beta = cum.swapaxes(2, 3), beta.swapaxes(2, 3)     # [B, c, H, Q]
    below = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(below, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bchik,bchjk->bchij", k, k, preferred_element_type=f32)
    qk = jnp.einsum("bchik,bchjk->bchij", q, k, preferred_element_type=f32)
    a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1),
                  by_value_head(kk) * decay * beta[..., None], 0.0)
    t_inv = gd.unit_lower_inverse(a).astype(dtype)
    attn = (by_value_head(qk) * decay).astype(dtype)
    kv, qv = by_value_head(k).astype(f32), by_value_head(q).astype(f32)
    u_own = jnp.einsum("bchij,bchjv->bchiv", t_inv,
                       (v.astype(f32) * beta[..., None]).astype(dtype),
                       preferred_element_type=f32)
    w = jnp.einsum("bchij,bchjk->bchik", t_inv,
                   (kv * (beta * jnp.exp(cum))[..., None]).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    q_in = (qv * jnp.exp(cum)[..., None]).astype(dtype)
    k_out = (kv * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)
    return tuple(jnp.moveaxis(t, 1, 0) for t in (u_own, w, attn, q_in, k_out))


def scan_recurrence(u_own, w, attn, q_in, k_out, decay, start):
    """The recurrence over chunks as ``gated_delta_chunked`` ran it before
    PR 36, on the kernels' arguments (``ops/gated_delta.py::_rec_fwd_call``)
    and in their outputs' order: a ``lax.scan`` whose turn is three batched
    products, the state through HBM, autodiff's backward."""
    f32, dtype = jnp.float32, w.dtype

    def one_chunk(state, now):
        u_c, w_c, q_c, k_c, attn_c, decay_c = now
        s = state.astype(dtype)
        u = (u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, s,
                              preferred_element_type=f32)).astype(dtype)
        o = jnp.einsum("bhik,bhkv->bhiv", q_c, s,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", attn_c, u,
                         preferred_element_type=f32)
        state = decay_c[..., None, None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, u, preferred_element_type=f32)
        return state, o.astype(dtype)

    final, o = jax.lax.scan(one_chunk, start, (
        u_own, w, q_in, k_out, attn, jnp.moveaxis(decay, 1, 0)))
    # [c, B, H, Q, V] -> [B, S, H V]
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3)
    return o.reshape(o.shape[0], -1, o.shape[3] * o.shape[4]), final


def rel(got, want) -> float:
    got, want = (t.astype(jnp.float32) for t in (got, want))
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


NAMES = ("u_own", "w", "attn", "q_in", "k_out")
REC_INPUTS = NAMES + ("decay", "start")
INPUTS = ("q", "k", "v", "cum", "beta")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--key-heads", type=int, default=16)
    parser.add_argument("--value-heads", type=int, default=32)
    parser.add_argument("--key-dim", type=int, default=128)
    parser.add_argument("--value-dim", type=int, default=128)
    parser.add_argument("--chunk", type=int, default=64)
    parser.add_argument("--substitute", type=int, nargs="*",
                        default=[gd._SUBSTITUTE])
    parser.add_argument("--chunks-per-block", type=int, nargs="*",
                        default=[gd._MAX_CHUNKS])
    parser.add_argument("--rec-heads", type=int, nargs="*",
                        default=[gd._REC_HEADS])
    parser.add_argument("--rec-chunks", type=int, nargs="*",
                        default=[gd._REC_CHUNKS])
    parser.add_argument("--rows", nargs="*",
                        default=["chunk_local", "recurrence", "whole"])
    parser.add_argument("--caller-norms", action="store_true",
                        help="normed rows in, no norm in the kernels")
    args = parser.parse_args()
    B, S, Hk, Hv, K, V, Q = (args.batch, args.seq, args.key_heads,
                             args.value_heads, args.key_dim, args.value_dim,
                             args.chunk)
    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind}",
          flush=True)
    c = S // Q
    ks = jax.random.split(jax.random.PRNGKey(0), 14)
    dtype, f32 = jnp.bfloat16, jnp.float32

    # The scale is the head's true size's, whatever lanes it rides.
    q_scale = None if args.caller_norms else K ** -0.5
    norm = dict(q_scale=q_scale)
    q = jax.random.normal(ks[0], (B, S, Hk, K))
    k = jax.random.normal(ks[1], (B, S, Hk, K))
    if args.caller_norms:
        q, k = gd.unit_rows(q, K ** -0.5), gd.unit_rows(k)
    q, k = q.astype(dtype), k.astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hv, V), dtype)
    published = (q, k, v)
    # What the kernels are called with: a head on whole lane tiles.
    q, k, v = (to_lanes(t) for t in published)
    K, V = q.shape[-1], v.shape[-1]
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (B, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, Hv))) \
        .reshape(B, c, Q, Hv)
    cum = jnp.cumsum(g.reshape(B, c, Q, Hv), axis=2)
    inputs = (q, k, v, cum, beta)
    like = jax.eval_shape(gd._fwd_call, *inputs)[:5]
    cts = tuple(jax.random.normal(key, t.shape, f32).astype(t.dtype)
                for key, t in zip(ks[5:10], like))

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "gdn_kernel_time.jsonl"),
               "a")

    def row(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def both(f, n=5):
        # The cotangents are arguments: closed over, 0.8 GB of them would be
        # constants of the program.
        def loss(*t):
            return sum(jnp.sum(o.astype(f32) * ct.astype(f32))
                       for o, ct in zip(f(*t[:n]), t[n:]))
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(n))))

    if "chunk_local" in args.rows:
        plain_fwd = functools.partial(plain_chunk_local, **norm)
        plain = both(plain_fwd)
        want = jax.jit(plain_fwd)(*inputs)
        _, want_g = plain(*inputs, *cts)
        row(what="plain", norm="caller" if args.caller_norms else "kernel",
            fwd_ms=timed(jax.jit(plain_fwd), *inputs),
            fwd_bwd_ms=timed(plain, *inputs, *cts))
        del plain
        shipped = gd._SUBSTITUTE, gd._MAX_CHUNKS
        for substitute in args.substitute:
            for chunks in args.chunks_per_block:
                gd._SUBSTITUTE, gd._MAX_CHUNKS = substitute, chunks
                jax.clear_caches()  # the calls are jitted: trace them anew
                fwd = jax.jit(lambda *t: gd._fwd_call(*t, **norm))
                bwd = jax.jit(lambda *t: gd._bwd_call(*t, **norm))
                kernels = both(lambda *t: gd._chunk_local(q_scale, *t))
                got = fwd(*inputs)
                _, got_g = kernels(*inputs, *cts)
                row(what="kernels", substitute=substitute,
                    chunks_per_block=gd.chunks_per_block(c),
                    fwd_ms=timed(fwd, *inputs),
                    bwd_ms=timed(bwd, *inputs, *cts, got[5]),
                    fwd_bwd_ms=timed(kernels, *inputs, *cts),
                    out_rel={n: rel(o, w)
                             for n, o, w in zip(NAMES, got, want)},
                    grad_rel={n: rel(o, w)
                              for n, o, w in zip(INPUTS, got_g, want_g)})
        gd._SUBSTITUTE, gd._MAX_CHUNKS = shipped
        del want, want_g, got, got_g
        jax.clear_caches()

    if "recurrence" in args.rows:
        rec = tuple(jax.jit(functools.partial(gd._fwd_call, **norm))(
            *inputs))[:5] + (
            jnp.exp(cum[:, :, -1]),
            0.1 * jax.random.normal(ks[11], (B, Hv, K, V), f32))
        rec_cts = (jax.random.normal(ks[12], (B, S, Hv * V), dtype),
                   jax.random.normal(ks[13], (B, Hv, K, V), f32))
        scan = both(scan_recurrence, 7)
        want = jax.jit(scan_recurrence)(*rec)
        _, want_g = scan(*rec, *rec_cts)
        row(what="scan", fwd_ms=timed(jax.jit(scan_recurrence), *rec),
            fwd_bwd_ms=timed(scan, *rec, *rec_cts))
        del scan
        shipped = gd._REC_HEADS, gd._REC_CHUNKS
        for heads in args.rec_heads:
            for chunks in args.rec_chunks:
                gd._REC_HEADS, gd._REC_CHUNKS = heads, chunks
                jax.clear_caches()
                fwd = jax.jit(lambda *t: gd._rec_fwd_call(*t, keep=False))
                keep = jax.jit(lambda *t: gd._rec_fwd_call(*t, keep=True))
                bwd = jax.jit(lambda *t: gd._rec_bwd_call(*t))
                # (No checkpoint here: the rule's one static argument,
                # whether the entering states are named, changes nothing.)
                kernels = both(lambda *t: gd._recurrence(False, *t), 7)
                got = fwd(*rec)
                entering = keep(*rec)[2]
                _, got_g = kernels(*rec, *rec_cts)
                out_rel = {n: rel(o, w) for n, o, w in zip(
                    ("o", "final"), got, want)}
                grad_rel = {n: rel(o, w) for n, o, w in zip(
                    REC_INPUTS, got_g, want_g)}
                row(what="recurrence", rec_heads=heads, rec_chunks=chunks,
                    fwd_ms=timed(fwd, *rec), fwd_keep_ms=timed(keep, *rec),
                    bwd_ms=timed(bwd, *rec[:6], entering, *rec_cts),
                    fwd_bwd_ms=timed(kernels, *rec, *rec_cts),
                    out_rel=out_rel, grad_rel=grad_rel,
                    largest_rel=max(*out_rel.values(), *grad_rel.values()))
                del entering, got, got_g
        gd._REC_HEADS, gd._REC_CHUNKS = shipped
        del rec, rec_cts, want, want_g
        jax.clear_caches()

    def whole(q, k, v, g, beta):
        o, final = gd.gated_delta_chunked(q, k, v, g, beta, chunk=Q,
                                          dtype=dtype,
                                          norm_qk=not args.caller_norms)
        return jnp.sum(jnp.sin(o.astype(f32))) + jnp.sum(final)

    if "whole" in args.rows:
        scan = (*published, g, beta.reshape(B, S, Hv))
        row(what="gated_delta_chunked", fwd_ms=timed(jax.jit(whole), *scan),
            fwd_bwd_ms=timed(jax.jit(jax.value_and_grad(
                whole, argnums=tuple(range(5)))), *scan))
    return 0


if __name__ == "__main__":
    sys.exit(main())
