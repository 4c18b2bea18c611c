#!/usr/bin/env python3
"""Time the causal depthwise convolution with its SiLU alone on the chip:
``ops/conv.py::causal_conv_silu`` (the kernels ``hvd_conv_fwd`` and
``hvd_conv_bwd``) against the ``jax.numpy`` lines it replaced,
``silu(causal_conv1d(u, w, b)).astype(u.dtype)`` under autodiff, and hold the
compiled kernels to them.

    chiprun --chips 1 -- python scripts/conv_kernel_time.py
    chiprun --chips 1 -- python scripts/conv_kernel_time.py --cells qwen \\
        --tokens-a-pass 128 256 512 --block 262144 524288 1048576

At the three cells' shapes (``qwen``: 4 x 4096 tokens of 8192 channels, no
bias; ``olmo``: 1 x 8192 of 11520, no bias; ``granite``: 2 x 4096 of 4352,
with a bias; bfloat16, four taps) and with either axis on the lanes, a row
holds ms a call, host clock around ``block_until_ready``, of the forward pass
and of the backward pass alone (the rule's kernel; for the plain lines the
vjp, which XLA computes with the forward's pre-activation made again), the
bytes a pass must move over that time as a share of 819 GB/s, and the largest
difference from the plain lines' values and gradients as a share of their
largest. ``--tokens-a-pass``, ``--passes`` and ``--block`` sweep the cut
(``ops/conv.py::_CONV_CUT``, ``_CONV_BLOCK``); without them a row is what
ships. Rows go to ``chiprun_out/conv_kernel_time.jsonl``. The ``tokens`` rows
get the tensor as ``[B, C, S]``, as a caller whose neighbours hold it so hands
it over; which form a mixer should ask for is what its neighbours hold
(``scripts/ssm_layer_time.py --trace --conv-minor``).

    chiprun --chips 1 -- python scripts/conv_kernel_time.py --cca \
        [--cca-tokens 256 512 1024 --cca-rows 64 128 256]

times instead a CCA mixer's mix (``ops/cca.py::cca_mix``: the kernels
``hvd_cca_fwd`` and ``hvd_cca_bwd``, which hold the depthwise stage too)
against ``cca_mix_reference``, the plain lines, at the ``zaya1-8b_s4096``
cell's shape (4 x 4096 tokens, 8:2 heads of 128, taps (2, 2), 64 rotary
dimensions at base 5e6; ``--cells tiny`` for a rehearsal off the chip): ms a
call, the bytes the call moves (``u`` read, ``q`` and ``k`` written; ``u``,
``dq``, ``dk`` read and ``du`` written) over that time in GB/s, and the largest
difference of ``q``, ``k`` and the six gradients from the plain lines' as a
share of their largest. ``--cca-tokens`` and ``--cca-rows`` sweep the cut (a
grid cell's tokens, a piece's: ``ops/cca.py::_TOKENS``, ``_ROWS``); without
them a row is what ships.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

# batch, tokens, channels, bias
CELLS = {"qwen": (4, 4096, 8192, False), "olmo": (1, 8192, 11520, False),
         "granite": (2, 4096, 4352, True),
         "tiny": (2, 300, 6, True)}  # a rehearsal off the chip
HBM_BYTES_PER_S = 819e9  # a v5e's, Google Cloud's "TPU v5e" page


def timed(fn, *args, reps: int = 20) -> float:
    import jax
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


# batch, tokens, query heads, key heads, head size, rotary dimensions
CCA_CELLS = {"zaya": (4, 4096, 8, 2, 128, 64), "tiny": (2, 72, 4, 2, 16, 8)}
CCA_OUTPUTS = ("q", "k", "du", "dconv0_w", "dconv0_b", "dconv1_w",
               "dconv1_b", "dtemp")


def cca_rows(args) -> int:
    """The rows of ``--cca``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import cca

    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind}",
          flush=True)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    f32, bf16 = jnp.float32, jnp.bfloat16
    name = "tiny" if "tiny" in args.cells else "zaya"
    batch, seq, heads, kv_heads, dim, rotary = CCA_CELLS[name]
    groups = heads + kv_heads
    wide = groups * dim
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    u = jax.random.normal(keys[0], (batch, seq, wide), bf16)
    weights = (
        jax.random.uniform(keys[1], (2, wide), f32, -0.707, 0.707),
        jax.random.uniform(keys[2], (wide,), f32, -0.707, 0.707),
        jax.random.uniform(keys[3], (2, groups, dim, dim), f32, -1, 1)
        / np.sqrt(2 * dim),
        jax.random.uniform(keys[4], (wide,), f32, -1, 1) / np.sqrt(2 * dim),
        0.1 * jax.random.normal(keys[5], (kv_heads,), f32))
    dq = jax.random.normal(keys[6], (batch, seq, heads, dim), bf16)
    dk = jax.random.normal(keys[7], (batch, seq, kv_heads, dim), bf16)
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    model = dict(heads=heads, kv_heads=kv_heads, rope_theta=5e6,
                 rotary_dim=rotary)
    moved = {"fwd": 2 * 2 * u.size, "bwd": 3 * 2 * u.size}

    def rel(got, want):
        got, want = got.astype(f32), want.astype(f32)
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    shipped = cca._TOKENS, cca._ROWS
    rows = [] if args.no_plain else [("plain", None, cca.cca_mix_reference)]
    for tokens, per in itertools.product(args.cca_tokens, args.cca_rows):
        rows.append(("kernels", (tokens or shipped[0], per or shipped[1]),
                     cca.cca_mix))
    want = None
    for form, cut, fn in rows:
        jax.clear_caches()
        cca._TOKENS, cca._ROWS = cut or shipped

        def mix(u, weights, fn=fn):
            return fn(u, *weights, positions, **model)

        fwd = jax.jit(mix)
        bwd = jax.jit(lambda u, weights, dq, dk, mix=mix: jax.vjp(
            mix, u, weights)[1]((dq, dk)))
        out = {"cell": name, "form": form, "device_kind": device.device_kind}
        if cut is not None:
            plan = cca._plan(seq, heads, kv_heads, dim, 2, 2, rotary)
            out.update(tokens=plan.tokens, rows=plan.rows)
        try:
            for leg, ms in (("fwd", timed(fwd, u, weights)),
                            ("bwd", timed(bwd, u, weights, dq, dk))):
                out[f"{leg}_ms"] = ms
                out[f"{leg}_gb_s"] = moved[leg] / (1e-3 * ms) / 1e9
            got = jax.tree.leaves((fwd(u, weights),
                                   bwd(u, weights, dq, dk)))
            if want is None:
                want = got
            else:
                out["off_by"] = dict(zip(CCA_OUTPUTS, (
                    rel(g, t) for g, t in zip(got, want, strict=True))))
        except Exception as e:  # a cut Mosaic refuses: the row says so
            out["error"] = str(e)[:300]
        line = json.dumps(out)
        print(line, flush=True)
        with open(os.path.join(HERE, "chiprun_out",
                               "cca_kernel_time.jsonl"), "a") as f:
            f.write(line + "\n")
        if "off_by" in out and max(out["off_by"].values()) > 2e-2:
            print(f"NOT EQUAL to the plain lines: {out['off_by']}")
            return 1
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", choices=list(CELLS),
                        default=["qwen", "olmo", "granite"])
    parser.add_argument("--minor", nargs="+", default=["channels", "tokens"])
    parser.add_argument("--tokens-a-pass", nargs="+", type=int, default=[0])
    parser.add_argument("--passes", nargs="+", type=int, default=[0])
    parser.add_argument("--block", nargs="+", type=int, default=[0])
    parser.add_argument("--no-plain", action="store_true")
    parser.add_argument("--cca", action="store_true",
                        help="time a CCA mixer's mix, not the convolution")
    parser.add_argument("--cca-tokens", nargs="+", type=int, default=[0])
    parser.add_argument("--cca-rows", nargs="+", type=int, default=[0])
    args = parser.parse_args()
    if args.cca:
        return cca_rows(args)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import conv

    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind}",
          flush=True)
    shipped_cut, shipped_block = dict(conv._CONV_CUT), conv._CONV_BLOCK
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    f32 = jnp.float32

    def plain(u, w, b):
        return jax.nn.silu(conv.causal_conv1d(u, w, b)).astype(u.dtype)

    def rel(got, want):
        got, want = got.astype(f32), want.astype(f32)
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    for cell in args.cells:
        batch, seq, channels, bias = CELLS[cell]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        u = jax.random.normal(keys[0], (batch, seq, channels), jnp.bfloat16)
        w = 0.5 * jax.random.normal(keys[1], (4, channels), f32)
        b = jax.random.normal(keys[2], (channels,), f32) if bias else None
        dy = jax.random.normal(keys[3], u.shape, jnp.bfloat16)
        nbytes = 2 * u.size
        rows = [("plain", None, plain)] if not args.no_plain else []
        for minor, sub, most, block in itertools.product(
                args.minor, args.tokens_a_pass, args.passes, args.block):
            rows.append((minor, (sub, most, block), None))
        want = None
        for name, cut, fn in rows:
            if cut is not None:
                # The calls are jitted inline: a cut traced once is kept.
                jax.clear_caches()
                axis, halo, sub, per, most = shipped_cut[name == "tokens"]
                conv._CONV_CUT[name == "tokens"] = (
                    axis, halo, cut[0] or sub, per, cut[1] or most)
                conv._CONV_BLOCK = cut[2] or shipped_block
                fn = functools.partial(conv.causal_conv_silu, minor=name)
                if name == "tokens":
                    # As a caller whose neighbours hold [B, C, S] hands the
                    # tensor over: the swaps either side are bitcasts.
                    fn = lambda ut, w, b: conv.causal_conv_silu(  # noqa: E731
                        ut.swapaxes(1, 2), w, b, minor="tokens").swapaxes(1, 2)
            fwd = jax.jit(fn)
            bwd = jax.jit(lambda u, w, b, dy, fn=fn: jax.vjp(fn, u, w, b)[1](
                dy))
            out = {"cell": cell, "form": name,
                   "device_kind": device.device_kind}
            if cut is not None:
                plan = conv._conv_plan("probe", seq, channels, u.dtype, 4,
                                       bias, name == "tokens")
                out.update(tile=f"{plan.tokens}x{plan.channels}",
                           tokens_a_pass=plan.sub, block=conv._CONV_BLOCK)
            turn = (lambda t: t.swapaxes(1, 2).copy()) if name == "tokens" \
                else (lambda t: t)
            try:
                out["fwd_ms"] = timed(fwd, turn(u), w, b)
                out["bwd_ms"] = timed(bwd, turn(u), w, b, turn(dy))
                out["fwd_hbm_pct"] = 100 * 2 * nbytes / HBM_BYTES_PER_S \
                    / (1e-3 * out["fwd_ms"])
                out["bwd_hbm_pct"] = 100 * 3 * nbytes / HBM_BYTES_PER_S \
                    / (1e-3 * out["bwd_ms"])
                got = (fwd(turn(u), w, b),) + tuple(
                    g for g in bwd(turn(u), w, b, turn(dy)) if g is not None)
                got = tuple(turn(g) if g.ndim == 3 else g for g in got)
                if want is None:
                    want = got
                else:
                    out["off_by"] = [rel(g, t) for g, t in zip(got, want)]
            except Exception as e:  # a cut Mosaic refuses: the row says so
                out["error"] = str(e)[:300]
            conv._CONV_CUT.update(shipped_cut)
            conv._CONV_BLOCK = shipped_block
            line = json.dumps(out)
            print(line, flush=True)
            with open(os.path.join(HERE, "chiprun_out",
                                   "conv_kernel_time.jsonl"), "a") as f:
                f.write(line + "\n")
            if "off_by" in out and max(out["off_by"]) > 2e-2:
                print(f"NOT EQUAL to the plain lines: {out['off_by']}")
                return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
