#!/usr/bin/env python3
"""Time the three flash kernels alone on the chip, by tile.

The source of ``ops/flash_attention.py``'s block table (PERF.md, Findings,
PR 24): each kernel jitted alone at a benchmark cell's shape, ``B*H,S,D`` =
48x4096x128 and 384x512x128 with K/V at 2 of 24 heads, over forced tiles,
operand dtypes and K/V head counts, timed on the host clock around
``block_until_ready`` (ms a call; a call is milliseconds, its dispatch tens
of microseconds). Also holds the compiled kernels to dense attention once.

    chiprun --chips 1 -- python scripts/flash_block_sweep.py [--quick]
    chiprun --chips 1 -- python scripts/flash_block_sweep.py --bwd [cell ...]
    chiprun --chips 1 -- python scripts/flash_block_sweep.py --dense-long
    chiprun --chips 1 -- python scripts/flash_block_sweep.py --grid [cell ...] [--tile BQ BK] [--layout rank3|rank4] [--repo DIR]

``--bwd`` times the backward pass alone at a benchmark cell's shape
(``BWD_SHAPES``: Moonlight's two widths and Trinity's window among them), as
the one kernel that makes dQ, dK and dV and as the pair it replaced (dKdV,
dQ and the lane-replicated statistics XLA makes for dQ), over three tiles,
and holds the one kernel's gradients to the pair's there (PERF.md, Findings,
PR 52).

``--dense-long`` holds the forward kernel and the one backward kernel to
``default_attention`` at the deepest shape a cell runs them at
(``LONG_SHAPE``: 16,384 rows at 28:4 heads of 128, under the causal mask and
under a 4096-key band), the dense side one query head at a time in float32
(PERF.md, Findings, PR 53); ``--dense-long sdar`` does it at 32:4 heads under
the block-diffusion mask at blocks of 4, the ``sdar-30b-a3b-chat_s8192``
cell's 80-tile grids (the cell's own check cannot tell a table that lacks one
tile from a sound seed: PERF.md, Findings, PR 65; a change to that mask's
tiling is held here first).

``--grid`` times the forward kernel and the one backward kernel at a cell's
shape and mask (``BWD_SHAPES``, all or those named) at the table's tile or a
forced one (``--tile``), beside the steps a head's grid walks and the tiles it
keeps: with ``--repo`` a copy of the commit before PR 61, whose grid was the
whole rectangle, the difference over the steps that did nothing is the price
of such a step (PERF.md, Findings, PR 61). ``--layout rank4`` hands the two
kernels their operands as ``[B, H, S, D]`` (what ``flash_attention`` hands
them where its caller asks since PR 70, ``heads_major``, as the attention
mixer does at two or more sequences of heads of one lane tile: the same bytes in the same order, a
batch axis squeezed out of every block),
``rank3`` (the default) merged to ``[B*H, S, D]``: the two side by side at a
shape say what the other index map costs a kernel (0.2-0.6% at the cells'
shapes: PERF.md, Findings, PR 70). The flag is this script's.

``--repo DIR`` times another checkout's kernels (a ``git archive`` of another
commit) with this script.

One JSON object a line on stdout and in ``chiprun_out/flash_sweep.jsonl``.
Works on a tree that still has the fixed-tile kernels (``--parent``): there
only the forward and the whole backward can be timed, at 128x128.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
fa = None     # ``horovod_tpu.ops.flash_attention`` of ``--repo``: ``main``

# The two cells, and two lengths between them at the same 8192 tokens.
SHAPES = {"s4096": (2, 4096, 24, 2, 128), "s512": (16, 512, 24, 2, 128),
          "s2048": (4, 2048, 24, 2, 128), "s1024": (8, 1024, 24, 2, 128)}
CELLS = ("s4096", "s512")
TILES = ((128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
         (512, 1024), (1024, 512), (1024, 1024), (1024, 2048), (2048, 1024),
         (512, 2048), (2048, 512))
# The attention of a cell's layers: (B, S, H, Hkv, D, Dv, window[, the block
# and the half of a block-diffusion mask]): what ``fa.Mask`` takes after
# ``causal``.
BWD_SHAPES = {
    "moonlight-16b-a3b_s8192": (2, 8192, 16, 16, 192, 128, None),
    "trinity-mini_s8192": (2, 8192, 32, 4, 128, 128, None),
    "trinity-mini_s8192_window": (2, 8192, 32, 4, 128, 128, 2048),
    "starcoder2-3b_s4096": (2, 4096, 24, 2, 128, 128, None),
    "zaya1-8b_s4096": (4, 4096, 8, 2, 128, 128, None),
    "qwen3-next-80b-a3b_s4096": (4, 4096, 16, 2, 256, 256, None),
    "olmo-hybrid-7b_s8192": (1, 8192, 30, 30, 128, 128, None),
    "granite-4.0-h-micro_s4096": (2, 4096, 32, 8, 64, 64, None),
    "starcoder2-3b_s512": (16, 512, 24, 2, 128, 128, None),
    "olmoe-1b-7b_s4096": (2, 4096, 16, 16, 128, 128, None),
    "smallthinker-21b-a3b_s16384": (1, 16384, 28, 4, 128, 128, None),
    "smallthinker-21b-a3b_s16384_window": (1, 16384, 28, 4, 128, 128, 4096),
    # One map of a differential attention layer: 20:10 pairs of heads, keys
    # of 64 beside values of 128, whole and under the band of 512.
    "phi-4-mini-flash-reasoning_s16384": (1, 16384, 20, 10, 64, 128, None),
    "phi-4-mini-flash-reasoning_s16384_window": (1, 16384, 20, 10, 64, 128,
                                                 512),
    "ouro-2.6b_s4096": (2, 4096, 16, 16, 128, 128, None),
    # One row of 16,384, the noised and the clean copy, in blocks of 4.
    "sdar-30b-a3b-chat_s8192": (1, 16384, 32, 4, 128, 128, None, 4, 8192),
}
BWD_TILES = ((1024, 1024), (512, 1024), (1024, 512))
# ``smallthinker-21b-a3b_s16384``'s attention: (B, S, H, Hkv, D), the full
# layer's mask and the window layers'.
LONG_SHAPE = BWD_SHAPES["smallthinker-21b-a3b_s16384"][:5]
LONG_MASKS = ({"window": None}, {"window": 4096})
# ``--dense-long CELL``: a shape and the masks (``flash_attention``'s and
# ``default_attention``'s keywords) it is held to dense attention under.
# ``sdar``: ``sdar-30b-a3b-chat_s8192``'s 16,384 rows under the
# block-diffusion mask, the 80-tile grids, beside the causal mask's.
LONG = {"smallthinker": (LONG_SHAPE, LONG_MASKS),
        "sdar": ((1, 16384, 32, 4, 128),
                 ({"block_diffusion": 4}, {"window": None}))}
OUT = os.path.join("chiprun_out", "flash_sweep.jsonl")
KERNELS = ("fwd", "dkdv", "dq")


def kernel_name(kernel: str) -> str:
    return getattr(fa, f"KERNEL_{kernel.upper()}")


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(fn, *args, iters: int = 10) -> float:
    """Median of three windows of ``iters`` calls, ms a call."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        windows.append((time.perf_counter() - t0) / iters * 1e3)
    return sorted(windows)[1]


def max_abs(x) -> float:
    return float(jnp.max(jnp.abs(x.astype(jnp.float32))))


def operands(shape, dtype, group_in_hbm: bool, seed: int = 0):
    """q, k, v, dO as the kernels take them, ``[B*H, S, D]``; K/V at their
    own head count, or repeated to the query's where ``group_in_hbm``."""
    b, s, h, hkv, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    hk = h if group_in_hbm else hkv
    q, do = (jax.random.normal(k, (b * h, s, d), dtype) * 0.5
             for k in ks[:2])
    k, v = (jax.random.normal(k, (b * hk, s, d), dtype) * 0.5
            for k in ks[2:])
    return q, k, v, do


def time_parent(name, shape, dtype):
    b, s, h, hkv, d = shape
    q, k, v, do = operands(shape, dtype, True)
    sc = 1.0 / d ** 0.5
    fwd = lambda q, k, v: fa._fwd_call(q, k, v, sc, True, s,
                                        fa._use_interpret())
    o, lse = jax.jit(fwd)(q, k, v)
    res = (q, k, v, o, lse[..., :1])
    bwd = lambda res, do: fa._flash_bhsd_bwd(sc, True, s, res, do)
    emit(tree="parent", shape=name, dtype=jnp.dtype(dtype).name,
         fwd_ms=timed(fwd, q, k, v), bwd_ms=timed(bwd, res, do))


def time_variant(label, name, shape, dtype, tiles, group_in_hbm, kernels):
    b, s, h, hkv, d = shape
    q, k, v, do = operands(shape, dtype, group_in_hbm)
    sc, causal = 1.0 / d ** 0.5, fa.Mask()
    o, lse = jax.jit(lambda q, k, v: fa._fwd_call(
        q, k, v, sc, causal, s, None))(q, k, v)
    lse = lse[..., 0]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    rows = (lse[:, None, :], delta[:, None, :])
    cols = tuple(jnp.broadcast_to(x[..., None], (*x.shape, 128))
                 for x in (lse, delta))
    row = dict(tree="change", variant=label, shape=name,
               dtype=jnp.dtype(dtype).name, kv_repeated=group_in_hbm)
    for kernel in kernels:
        for t in tiles:
            if t is not None and (s % t[0] or s % t[1]):
                continue
            if kernel == "fwd":
                f = lambda q, k, v: fa._fwd_call(q, k, v, sc, causal, s, t)
                args = (q, k, v)
            elif kernel == "dkdv":
                f = lambda *a: fa._dkdv_call(*a, sc, causal, s, t)
                args = (q, k, v, do, *rows)
            else:
                f = lambda *a: fa._dq_call(*a, sc, causal, s, t)
                args = (q, k, v, do, *cols)
            try:
                ms = timed(f, *args)
            except Exception as e:  # a tile Mosaic refuses is a result too
                emit(**row, kernel=kernel, tile=t, error=str(e)[:300])
                continue
            chosen = t or fa.block_sizes(kernel_name(kernel), s, d, dtype,
                                         True)
            emit(**row, kernel=kernel, tile=list(chosen),
                 table=t is None, ms=ms)


def bwd_operands(name, dtype, layout="rank3"):
    """q, k, v, dO as the kernels take them at ``BWD_SHAPES[name]``, a head
    a row of the first axis or (``rank4``) ``[B, H, S, D]``; the logits'
    scale and the mask."""
    b, s, h, hkv, d, dv, *mask = BWD_SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)

    def operand(key, heads, width):
        shape = (b * heads, s, width) if layout == "rank3" \
            else (b, heads, s, width)
        return jax.random.normal(key, shape, dtype) * 0.5

    q, k = operand(ks[0], h, d), operand(ks[1], hkv, d)
    v, do = operand(ks[2], hkv, dv), operand(ks[3], h, dv)
    return q, k, v, do, 1.0 / d ** 0.5, fa.Mask(True, *mask)


def time_grid(name, tile=None, dtype=jnp.bfloat16, layout="rank3"):
    """The forward kernel and the one backward kernel at cell ``name``'s
    shape and mask, ms a call (``delta`` made in the backward's), at the
    table's tile or ``tile``; beside them the tiles of a head's rectangle,
    those the mask keeps, and the steps its grid walks (a tree whose grid is
    still the rectangle has no ``Mask.kept_tiles`` and walks them all).
    ``layout``: how the operands lie (``bwd_operands``)."""
    b, s, h, hkv, d, dv = BWD_SHAPES[name][:6]
    q, k, v, do, sc, mask = bwd_operands(name, dtype, layout)
    bq, bk = tile or fa.block_sizes(fa.KERNEL_DKDV, s, d, dtype, True, dv)
    tiles = mask.tiles(s // bq, s // bk, bq, bk)
    row = dict(grid=name, layout=layout, tile=[bq, bk],
               dtype=jnp.dtype(dtype).name,
               heads=b * h, rectangle=sum(tiles.values()),
               kept=tiles["kept"],
               steps=tiles["kept"] if hasattr(mask, "kept_tiles")
               else sum(tiles.values()))
    fwd = lambda q, k, v: fa._fwd_call(q, k, v, sc, mask, s, (bq, bk))
    o, lse = jax.jit(fwd)(q, k, v)
    lse = lse[:, None, :, 0]

    def bwd(q, k, v, o, do):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        return fa._bwd_call(q, k, v, do, lse,
                            delta.reshape(b * h, 1, s), sc, mask, s, (bq, bk))

    try:
        row["fwd_ms"] = timed(fwd, q, k, v)
        row["bwd_ms"] = timed(bwd, q, k, v, o, do)
    except Exception as e:  # a tile Mosaic refuses is a result too
        row["error"] = str(e)[:300]
    emit(**row)


def time_bwd(name, tiles=BWD_TILES, dtype=jnp.bfloat16):
    """The backward pass at cell ``name``'s shape: the fused kernel beside
    the pair (dKdV, dQ and the statistics' broadcast to lanes that dQ
    reads), ms a call with ``delta`` made in both, and the largest
    difference of their gradients."""
    q, k, v, do, sc, mask = bwd_operands(name, dtype)
    s, d, dv = q.shape[1], q.shape[2], v.shape[2]
    o, lse = jax.jit(lambda q, k, v: fa._fwd_call(
        q, k, v, sc, mask, s, None))(q, k, v)
    lse = lse[..., 0]

    def stats(o, do):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        return lse[:, None, :], delta[:, None, :]

    def lanes(x):
        return jnp.broadcast_to(x[:, 0, :, None], (*x.shape[::2], 128))

    for t in tiles:
        if s % t[0] or s % t[1]:
            t = (min(t[0], s), min(t[1], s))

        def fused(q, k, v, o, do):
            return fa._bwd_call(q, k, v, do, *stats(o, do), sc, mask, s, t)

        def pair(q, k, v, o, do):
            rows = stats(o, do)
            dk, dv_ = fa._dkdv_call(q, k, v, do, *rows, sc, mask, s, t)
            dq = fa._dq_call(q, k, v, do, *map(lanes, rows), sc, mask, s, t)
            return dq, dk, dv_

        row = dict(bwd=name, tile=list(t), dtype=jnp.dtype(dtype).name,
                   fits=fa.backward_is_fused(*t, s, d, dtype, dv))
        try:
            row["fused_ms"] = timed(fused, q, k, v, o, do)
            row["pair_ms"] = timed(pair, q, k, v, o, do)
            got = jax.jit(fused)(q, k, v, o, do)
            want = jax.jit(pair)(q, k, v, o, do)
            names = ("dq", "dk", "dv")
            row["max_abs_diff"] = dict(zip(names, (
                max_abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                for a, b in zip(got, want))))
            row["max_abs"] = dict(zip(names, map(max_abs, want)))
        except Exception as e:  # a tile Mosaic refuses is a result too
            row["error"] = str(e)[:300]
        emit(**row)
        if t == (s, s):
            break


DENSE_CASES = (
    (jnp.float32, 1024, True, None), (jnp.float32, 1024, True, (256, 512)),
    (jnp.float32, 1024, True, (512, 256)), (jnp.float32, 600, False, None),
    (jnp.float32, 600, True, None), (jnp.bfloat16, 4096, True, None),
    (jnp.bfloat16, 512, True, None), (jnp.bfloat16, 1024, False, (512, 256)))


def check_against_dense(cases=DENSE_CASES):
    """The compiled kernels against dense attention: GQA, causal and not,
    an unaligned length, tiles the table gives and unequal forced ones."""
    from horovod_tpu.ops.attention import default_attention, repeat_kv_heads
    for dtype, s, causal, blocks in cases:
        ks = jax.random.split(jax.random.PRNGKey(s), 4)
        q = jax.random.normal(ks[0], (1, s, 4, 128), dtype) * 0.5
        k = jax.random.normal(ks[1], (1, s, 2, 128), dtype) * 0.5
        v = jax.random.normal(ks[2], (1, s, 2, 128), dtype) * 0.5
        w = jax.random.normal(ks[3], q.shape, jnp.float32)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

        flash = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, _blocks=blocks)
        # The reference in float32 at the highest precision (XLA's default
        # on the chip is one bfloat16 pass, coarser than the kernels).
        def dense(q, k, v):
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            with jax.default_matmul_precision("highest"):
                return default_attention(
                    q, repeat_kv_heads(k, 4), repeat_kv_heads(v, 4),
                    causal=causal)
        got = jax.jit(jax.value_and_grad(
            lambda *a: loss(flash, *a), argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(
            lambda *a: loss(dense, *a), argnums=(0, 1, 2)))(q, k, v)
        errs = [max_abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        scale = [max_abs(b) for b in jax.tree.leaves(want)]
        emit(check="dense", dtype=jnp.dtype(dtype).name, s=s, causal=causal,
             blocks=blocks, max_abs_err=dict(zip(("loss", "dq", "dk", "dv"),
                                                 errs)),
             max_abs=dict(zip(("loss", "dq", "dk", "dv"), scale)))


def check_long_against_dense(shape=LONG_SHAPE, masks=LONG_MASKS,
                             dtype=jnp.bfloat16):
    """The compiled forward and (one) backward kernel through
    ``flash_attention`` at ``shape`` against ``default_attention`` on the
    same bfloat16 operands in float32 at the highest precision. An ``S x S``
    float32 matrix a head is 1 GiB at 16,384 rows, so the dense side is made
    one query head at a time and a K/V head's gradient summed over its
    group. Errors are largest differences beside the dense side's largest
    value; the kernels round their probabilities to bfloat16 for the MXU,
    so 2**-8 of a value is the size to expect."""
    from horovod_tpu.ops.attention import default_attention
    b, s, h, hkv, d = shape
    group = h // hkv
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype) * 0.5
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype) * 0.5
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype) * 0.5
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    for mask in masks:
        tile = fa.block_sizes(fa.KERNEL_DKDV, s, d, dtype, True)
        flash = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, causal=True, **mask).astype(jnp.float32)
                * w), argnums=(0, 1, 2)))

        @jax.jit
        def dense_head(q1, k1, v1, w1):
            """One query head: its output weighed and summed, dq, and its
            part of dk and dv."""
            def loss(q1, k1, v1):
                with jax.default_matmul_precision("highest"):
                    return jnp.sum(default_attention(
                        q1, k1, v1, causal=True, **mask) * w1)
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(
                *(x.astype(jnp.float32) for x in (q1, k1, v1)))

        got = jax.device_get(flash(q, k, v))
        want_loss = 0.0
        dq = np.zeros(q.shape, np.float32)
        dk, dv = (np.zeros(k.shape, np.float32) for _ in range(2))
        for head in range(h):
            kv = slice(head // group, head // group + 1)
            val, (gq, gk, gv) = dense_head(q[:, :, head:head + 1], k[:, :, kv],
                                           v[:, :, kv], w[:, :, head:head + 1])
            want_loss += float(val)
            dq[:, :, head:head + 1] = np.asarray(gq)
            dk[:, :, kv] += np.asarray(gk)
            dv[:, :, kv] += np.asarray(gv)
        names = ("dq", "dk", "dv")
        emit(check="dense_long", shape=list(shape), mask=mask,
             dtype=jnp.dtype(dtype).name, tile=list(tile),
             backward_is_fused=fa.backward_is_fused(*tile, s, d, dtype, d),
             loss=[float(got[0]), want_loss],
             max_abs_err={n: float(np.max(np.abs(
                 np.asarray(a, np.float32) - b_)))
                 for n, a, b_ in zip(names, got[1], (dq, dk, dv))},
             # The kernels' gradient along the dense side's, less one: a
             # table that lacks a tile shortens it.
             along_less_one={n: float(np.vdot(b_, np.asarray(a, np.float32))
                                      / np.vdot(b_, b_) - 1)
                             for n, a, b_ in zip(names, got[1], (dq, dk, dv))},
             # A norm of the difference over the dense side's norm.
             rel_l2_err={n: float(np.linalg.norm(
                 np.asarray(a, np.float32) - b_) / np.linalg.norm(b_))
                 for n, a, b_ in zip(names, got[1], (dq, dk, dv))},
             max_abs={n: float(np.max(np.abs(b_)))
                      for n, b_ in zip(names, (dq, dk, dv))})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", action="store_true",
                    help="the tree has the fixed-tile kernels")
    ap.add_argument("--quick", action="store_true",
                    help="the table's choice only, no sweep")
    ap.add_argument("--bwd", nargs="*", metavar="CELL", default=None,
                    help="the backward pass alone, one kernel beside the "
                         "pair, at these cells' shapes (none named: all)")
    ap.add_argument("--dense-long", nargs="?", const="smallthinker",
                    choices=sorted(LONG), metavar="CELL",
                    help="the forward and the one backward kernel against "
                         "dense attention at 16,384 rows and nothing else: "
                         "28:4 heads, causal and under a 4096 band "
                         "(smallthinker, the default), or 32:4 heads under "
                         "the block-diffusion mask at blocks of 4 and "
                         "causal (sdar)")
    ap.add_argument("--grid", nargs="*", metavar="CELL", default=None,
                    help="the forward and the one backward kernel at these "
                         "cells' shapes and masks (none named: all), beside "
                         "the steps a head's grid walks")
    ap.add_argument("--tile", nargs=2, type=int, metavar=("BQ", "BK"),
                    help="with --grid: this tile, not the table's")
    ap.add_argument("--layout", nargs="+", choices=("rank3", "rank4"),
                    default=["rank3"],
                    help="with --grid: how the operands come, merged to "
                         "[B*H, S, D] or [B, H, S, D] (both: side by side "
                         "at each shape; rank4 needs a tree since PR 70)")
    ap.add_argument("--repo", default=HERE,
                    help="the checkout whose kernels are timed")
    args = ap.parse_args()
    global fa
    sys.path.insert(0, os.path.abspath(args.repo))
    from horovod_tpu.ops import flash_attention as fa
    dev = jax.devices()[0]
    emit(platform=dev.platform, device_kind=dev.device_kind,
         repo=os.path.abspath(args.repo))
    if dev.platform != "tpu":
        sys.exit("flash_block_sweep.py times kernels on a TPU; found "
                 f"{dev.platform}")
    bf16, f32 = jnp.bfloat16, jnp.float32
    if args.parent:
        for name, shape in SHAPES.items():
            time_parent(name, shape, bf16)
        return
    if args.dense_long:
        check_long_against_dense(*LONG[args.dense_long])
        return
    if args.grid is not None:
        for name in args.grid or BWD_SHAPES:
            for layout in args.layout:
                time_grid(name, args.tile and tuple(args.tile),
                          layout=layout)
        return
    check_against_dense()
    if args.bwd is not None:
        for name in args.bwd or BWD_SHAPES:
            time_bwd(name)
        return
    for name, shape in SHAPES.items():
        time_variant("table", name, shape, bf16, (None,), False, KERNELS)
        if args.quick:
            continue
        time_variant("sweep", name, shape, bf16, TILES, False, KERNELS)
        if name not in CELLS:
            continue
        best = tuple(fa.block_sizes(kernel_name(k), shape[1], 128, bf16,
                                    True) for k in KERNELS)
        # The parts alone, from the old tile: float32 operands, 128x128,
        # K/V repeated in HBM; then each part by itself.
        small = ((128, 128),)
        time_variant("bookkeeping_only", name, shape, f32, small, True,
                     KERNELS)
        time_variant("operands_alone", name, shape, bf16, small, True,
                     KERNELS)
        time_variant("gqa_alone", name, shape, f32, small, False,
                     KERNELS)
        for kernel, tile in zip(KERNELS, best):
            time_variant("blocks_alone", name, shape, f32, (tile,), True,
                         (kernel,))


if __name__ == "__main__":
    main()
