#!/usr/bin/env python3
"""Time the state-space scan's within-chunk kernels alone on the chip, and
hold the compiled kernels to the ``jax.numpy`` expression they replaced.

    chiprun --chips 1 -- python scripts/ssd_kernel_time.py [--heads-per-block 4 8 16]

At the ``granite-4.0-h-micro_s4096`` cell's shapes (2 x 4096 tokens, 64 heads
of 64, state 128, one group, chunk 256, bfloat16) it jits and times, host
clock around ``block_until_ready``: ``hvd_ssd_fwd``; ``hvd_ssd_bwd``; the
scan's output forward and backward through the ``custom_vjp``; the same
through the plain expression (XLA writes the ``[chunk, chunk]`` tensors to
HBM); and ``ssd_chunked`` whole, forward and backward. The kernels' values
and gradients are compared with the plain expression's (relative to the
largest value). ``--heads-per-block`` forces the heads a grid cell holds (the
source of ``ops/ssd.py::_MAX_HEADS``). One JSON line a row, also appended to
``chiprun_out/ssd_kernel_time.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.ops import ssd  # noqa: E402


def timed(fn, *args, reps: int = 10) -> float:
    """ms a call: the mean of ``reps`` calls after two warm ones."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def plain_scan_output(x, dt, b_in, c_in, cum, through, d):
    """What ``ssd_chunked`` computed before the kernels, on their arguments
    (``ops/ssd.py::_fwd_call``): ``[B, c, H, Q, Q]`` decays and weights
    through HBM, the entering state's part and the skip added by XLA,
    autodiff's backward."""
    groups = b_in.shape[2]
    n_chunks, chunk = through.shape[1], through.shape[-1]

    def chunked(t):
        return t.reshape(t.shape[:1] + (n_chunks, chunk) + t.shape[2:])

    f32 = jnp.float32
    cum = chunked(cum).swapaxes(2, 3)                       # [B, c, H, Q]
    cb = jnp.einsum("bcign,bcjgn->bcgij", chunked(c_in), chunked(b_in),
                    preferred_element_type=f32)
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(keep, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    weights = (ssd._by_group(decay, groups, 2)
               * cb[:, :, :, None]).astype(x.dtype)
    xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype)
    y = jnp.einsum("bcgkij,bcjgkp->bcigkp", weights,
                   ssd._by_group(chunked(xdt), groups, 3),
                   preferred_element_type=f32)
    y = y.reshape(chunked(x).shape) + jnp.moveaxis(through, 4, 2) \
        * jnp.exp(cum).swapaxes(2, 3)[..., None]
    return (y.reshape(x.shape)
            + d[:, None] * x.astype(f32)).astype(x.dtype)


def rel(got, want) -> float:
    got, want = (t.astype(jnp.float32) for t in (got, want))
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--state", type=int, default=128)
    parser.add_argument("--groups", type=int, default=1)
    parser.add_argument("--chunk", type=int, default=256)
    parser.add_argument("--heads-per-block", type=int, nargs="*",
                        default=[ssd._MAX_HEADS])
    args = parser.parse_args()
    B, S, H, P, N, G, Q = (args.batch, args.seq, args.heads, args.head_dim,
                           args.state, args.groups, args.chunk)
    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind}",
          flush=True)
    c = S // Q
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    dtype = jnp.bfloat16
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0., maxval=2.7))
    b_in = jax.random.normal(ks[3], (B, S, G, N), dtype)
    c_in = jax.random.normal(ks[4], (B, S, G, N), dtype)
    d = jax.random.normal(ks[5], (H,))
    dy = jax.random.normal(ks[6], (B, S, H, P), dtype)
    cum = jnp.cumsum((dt * a).reshape(B, c, Q, H), axis=2).reshape(B, S, H)
    through = jax.random.normal(ks[7], (B, c, H, P, Q), jnp.float32)
    inputs = (x, dt, b_in, c_in, cum, through, d)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "ssd_kernel_time.jsonl"),
               "a")

    def row(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def both(f):
        def loss(*t):
            return jnp.sum(f(*t) * dy.astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7))))

    plain = both(plain_scan_output)
    want_y = jax.jit(plain_scan_output)(*inputs)
    _, want_g = plain(*inputs)
    row(what="plain", fwd_ms=timed(jax.jit(plain_scan_output), *inputs),
        fwd_bwd_ms=timed(plain, *inputs))
    for hb in args.heads_per_block:
        ssd._MAX_HEADS = hb
        fwd = jax.jit(lambda *t: ssd._fwd_call(*t))
        bwd = jax.jit(lambda *t: ssd._bwd_call(*t))
        kernels = both(lambda *t: ssd._scan_output(*t))
        got_y = fwd(*inputs)
        _, got_g = kernels(*inputs)
        row(what="kernels", heads_per_block=ssd.heads_per_block(H // G),
            fwd_ms=timed(fwd, *inputs), bwd_ms=timed(bwd, *inputs, dy),
            fwd_bwd_ms=timed(kernels, *inputs),
            y_rel=rel(got_y, want_y),
            grad_rel={n: rel(g, w) for n, g, w in zip(
                ("x", "dt", "B", "C", "cum", "through", "D"), got_g,
                want_g)})
    ssd._MAX_HEADS = args.heads_per_block[0]

    def whole(x, dt, a, b_in, c_in, d):
        y, final = ssd.ssd_chunked(x, dt, a, b_in, c_in, d, chunk=Q,
                                   dtype=dtype)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))) + jnp.sum(final)

    scan = (x, dt, a, b_in, c_in, d)
    row(what="ssd_chunked", fwd_ms=timed(jax.jit(whole), *scan),
        fwd_bwd_ms=timed(jax.jit(jax.value_and_grad(
            whole, argnums=tuple(range(6)))), *scan))
    return 0


if __name__ == "__main__":
    sys.exit(main())
