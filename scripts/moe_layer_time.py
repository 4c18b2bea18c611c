#!/usr/bin/env python3
"""Time the expert layer alone on the chip, whole and in parts.

    chiprun --chips 1 -- python scripts/moe_layer_time.py

At the ``olmoe-1b-7b_s4096`` cell's shapes (8192 tokens of 2048, 64 experts
of 1024, 8 a token, bfloat16) it jits and times, host clock around
``block_until_ready``: the layer's forward pass; forward and backward;
forward and backward under ``jax.checkpoint``, as a ``remat="full"`` block
runs the layer, keeping nothing and keeping what ``models/gpt.py::
SAVED_NAMES`` lists (the layer's share of a change to that list, before the
cell is run); the three grouped matmuls on sorted rows alone, forward and
with their backward;
the two row permutations alone; and the layer against the every-expert-on-
every-token reference on 1024 tokens. One JSON line a row, also appended to
``chiprun_out/moe_layer_time.jsonl``. ``--skew`` routes every token to the
first ``top_k`` experts (the router's columns made equal: ties go to the
lower index). ``--routing`` times the checkpointed layer alone, by what of
its routing the checkpoint keeps (``routing_rows``: the price of the names
PR 54 added to ``SAVED_NAMES`` and of the form the chosen scores are read
in; two minutes a shape). ``--first-products`` does the same by what of its
first products it keeps where the layer works on all its rows at once
(``first_product_rows``: the price of the names PR 59 added, the sorted rows
and the gate and up products before the activation; ZAYA1's share is
``--router-width 16 --experts 8 --tokens 16384 --top-k 1 --width 2048``).

A rank's share (``--router-width`` wider than ``--experts``; the
``qwen3-next-80b-a3b_s4096`` cell's is ``--router-width 512 --experts 32
--first-expert 0 --tokens 16384 --top-k 10 --width 512 --renormalize``): the
same rows for one window of the order, as many rows as the layer works on at
a time (``moe.share_rows``), the row gather as ``moe._take``, and a row for
each form of the sum back to tokens on ``[R, d]`` float32 rows of the run's
own routing: ``moe._to_tokens`` and the form not chosen (PERF.md, Findings,
PR 32). With ``--skew`` and ``--first-expert 0`` every row is the share's
and the layer takes ``T k / R`` windows; ``--all-rows`` times the share as
it ran before PR 32, on all the rows at once. ``--trace`` also profiles four
calls of the checkpointed layer's forward and backward and prints the windows
a call's forward rule took as the benchmark's ``moe_windows_per_step`` counts
them in a device trace (``windows_traced``, beside ``windows`` from the
counts: 1.0 for the cell's share under an even routing, 8.0 under
``--skew``).
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
from jax import lax  # noqa: E402

from horovod_tpu.models import gpt  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402


def timed(fn, *args, reps: int = 10) -> float:
    """ms a call: the mean of ``reps`` calls after two warm ones."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def to_tokens_by_gathers(vals, pair_of_row, tokens, top_k):
    """``moe._to_tokens`` without a scatter: the rows brought to token order
    (a sort of ``R`` pair numbers and a gather of ``R`` rows), neighbours of
    one token added in doubling steps (a token has at most ``top_k`` rows, so
    shifts of 1, 2, 4, ... under a same-token mask finish every sum), and
    each token's last row read (a search and a gather of ``tokens`` rows)."""
    by_token = jnp.argsort(pair_of_row)
    token = pair_of_row[by_token] // top_k
    z = vals.astype(jnp.float32)[by_token]
    shift = 1
    while shift < top_k:
        same = (token[shift:] == token[:-shift])[:, None]
        z = z + jnp.pad(jnp.where(same, z[:-shift], 0), ((shift, 0), (0, 0)))
        shift *= 2
    last = jnp.searchsorted(token, jnp.arange(tokens), side="right") - 1
    has = (last >= 0) & (token[jnp.maximum(last, 0)] == jnp.arange(tokens))
    return jnp.where(has[:, None], z[jnp.maximum(last, 0)], 0)


def to_tokens_rows(h, router, first, held, top_k, rows) -> dict:
    """ms of each form of the sum back to tokens, on ``rows`` float32 rows
    as wide as the tokens and as wide as ``top_k`` (the routing weights'
    cotangent), for the pairs the layer's own routing puts first; and the
    largest difference between the forms."""
    tokens, d = h.shape
    probs = jax.nn.softmax(jnp.dot(h.astype(jnp.float32), router), axis=-1)
    top_e = lax.top_k(probs, top_k)[1].reshape(-1)
    order = jnp.argsort((top_e - first) % router.shape[1], stable=True)
    pair_of_row = order[:rows]
    n = jnp.sum((top_e - first) % router.shape[1] < held)
    out = {}
    forms = {"scatter_add": moe._to_tokens, "gathers": to_tokens_by_gathers}
    for width in (d, top_k):
        vals = jax.random.normal(jax.random.PRNGKey(7), (rows, width),
                                 jnp.float32)
        vals = jnp.where((jnp.arange(rows) < n)[:, None], vals, 0)
        got = {}
        for name, form in forms.items():
            f = jax.jit(lambda v, p, form=form: form(v, p, tokens, top_k))
            out[f"{name}_w{width}"] = timed(f, vals, pair_of_row)
            got[name] = f(vals, pair_of_row)
        out[f"forms_differ_by_w{width}"] = float(jnp.max(jnp.abs(
            got["scatter_add"] - got["gathers"])))
    # The parts, alone: the sorts and the two gathers.
    out["argsort_pairs"] = timed(jax.jit(
        lambda e: jnp.argsort(e, stable=True)), top_e)
    out["argsort_rows"] = timed(jax.jit(jnp.argsort), pair_of_row)
    vals = jax.random.normal(jax.random.PRNGKey(8), (rows, d), jnp.float32)
    out["gather_rows_f32"] = timed(jax.jit(lambda v, p: v[p]), vals,
                                   jnp.argsort(pair_of_row))
    out["gather_tokens_f32"] = timed(jax.jit(lambda v, p: v[p]), vals,
                                     pair_of_row[:tokens] % rows)
    return out


def emit(line: str) -> int:
    """The row printed and appended to ``chiprun_out/moe_layer_time.jsonl``."""
    print(line, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_layer_time.jsonl"),
              "a") as f:
        f.write(line + "\n")
    return 0


def first_product_rows(checkpointed, h, weights) -> dict:
    """ms of the checkpointed layer's forward and backward by what of its
    first products the checkpoint keeps: neither (the gather of the sorted
    rows and the gate and up products made again: the step before PR 59),
    the two pre-activations alone, and the sorted rows with them (all that
    ``gpt.SAVED_NAMES`` lists). Each row twice, in turn."""
    first = ("moe_rows", "moe_pre_activation")
    others = [name for name in gpt.SAVED_NAMES if name not in first]
    rows = {"first_products_made_again": checkpointed(*others),
            "pre_activation_kept": checkpointed(*others,
                                                "moe_pre_activation"),
            "SAVED_NAMES": checkpointed(*gpt.SAVED_NAMES)}
    out = {name: [timed(f, h, *weights)] for name, f in rows.items()}
    for name, f in rows.items():
        out[name].append(timed(f, h, *weights))
    return out


def routing_rows(checkpointed, h, weights) -> dict:
    """ms of the checkpointed layer's forward and backward by what of its
    routing the checkpoint keeps: nothing of it (the router's product, the
    full-row sort, the argsort made again: the step before PR 54), the
    router's outputs alone, all that ``gpt.SAVED_NAMES`` lists, and the last
    with the chosen scores read by ``take_along_axis`` in place of
    ``moe._scores_at``'s one-hot product (the form not chosen). Each row
    twice, in turn."""
    routing = {name for name in gpt.SAVED_NAMES
               if name.startswith(("moe_router", "moe_top", "moe_order"))}
    others = [name for name in gpt.SAVED_NAMES if name not in routing]
    rows = {"routing_made_again": checkpointed(*others),
            "router_logits_kept": checkpointed(*others, "moe_router_logits"),
            "SAVED_NAMES": checkpointed(*gpt.SAVED_NAMES)}
    out = {name: [timed(f, h, *weights)] for name, f in rows.items()}
    one_hot, moe._scores_at = moe._scores_at, lambda probs, top_e: \
        jnp.take_along_axis(probs, top_e, axis=-1)
    try:
        rows["SAVED_NAMES_scores_by_gather"] = checkpointed(*gpt.SAVED_NAMES)
        out["SAVED_NAMES_scores_by_gather"] = [timed(
            rows["SAVED_NAMES_scores_by_gather"], h, *weights)]
    finally:
        moe._scores_at = one_hot
    for name, f in rows.items():
        out[name].append(timed(f, h, *weights))
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--embed", type=int, default=2048)
    parser.add_argument("--width", type=int, default=1024)
    parser.add_argument("--experts", type=int, default=64)
    parser.add_argument("--top-k", type=int, default=8)
    parser.add_argument("--skew", action="store_true")
    parser.add_argument("--router-width", type=int, default=None,
                        help="experts routed over (default: --experts, "
                        "every one held)")
    parser.add_argument("--first-expert", type=int, default=0)
    parser.add_argument("--renormalize", action="store_true")
    parser.add_argument("--all-rows", action="store_true",
                        help="a share on all T k rows, as before PR 32 "
                        "(the headroom raised until a window is every row)")
    parser.add_argument("--trace", action="store_true")
    parser.add_argument("--routing", action="store_true",
                        help="only the checkpointed layer, by what of its "
                        "routing the checkpoint keeps (routing_rows)")
    parser.add_argument("--first-products", action="store_true",
                        help="only the checkpointed layer, by what of its "
                        "first products the checkpoint keeps "
                        "(first_product_rows)")
    args = parser.parse_args()
    T, d, m, E, k = (args.tokens, args.embed, args.width, args.experts,
                     args.top_k)
    wide, first = args.router_width or E, args.first_expert
    if args.all_rows:
        moe.SHARE_HEADROOM = -(-wide // E)
    R = moe.share_rows(T, k, E, wide) if E < wide else T * k
    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind}",
          flush=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    h = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
    router = jax.random.normal(ks[1], (d, wide), jnp.float32) / d ** 0.5
    if args.skew:
        router = jnp.broadcast_to(router[:, :1], router.shape)
    w_gate = jax.random.normal(ks[2], (E, d, m), jnp.float32) / d ** 0.5
    w_up = jax.random.normal(ks[3], (E, d, m), jnp.float32) / d ** 0.5
    w_down = jax.random.normal(ks[4], (E, m, d), jnp.float32) / m ** 0.5
    weights = (router, w_gate, w_up, w_down)

    moe_layer = functools.partial(moe.moe_layer, top_k=k, first_expert=first,
                                  renormalize=args.renormalize)

    def layer(h, *w):
        with jax.named_scope("moe"):        # as models/gpt.py::_block calls it
            y, aux = moe_layer(h, *w)
        return jnp.sum(y.astype(jnp.float32)) + aux["load_balance"] \
            + aux["router_z"], aux["counts"]

    _, counts = jax.jit(layer)(h, *weights)
    counts = counts[first:first + E]
    rows_held = int(counts.sum())
    busiest = float(counts.max() * E / counts.sum())
    # The rows' table works on one window: its part of every expert's rows.
    ends = jnp.minimum(jnp.cumsum(counts), R)
    counts = jnp.diff(ends, prepend=0).astype(counts.dtype)
    rows_in = jax.random.normal(ks[5], (R, d), jnp.bfloat16)
    perm = jax.random.permutation(ks[5], T * k)
    inv = jnp.argsort(perm)

    def experts(rows, w_gate, w_up, w_down):
        def gmm(lhs, w):
            return lax.ragged_dot(lhs, w.astype(jnp.bfloat16), counts)
        hidden = jax.nn.silu(gmm(rows, w_gate)) * gmm(rows, w_up)
        return jnp.sum(gmm(hidden, w_down).astype(jnp.float32))

    def permute(rows):
        if R < T * k:       # a share's window: R of the tokens' rows
            taken = moe._take(h, perm[:R], k)
        else:
            taken = moe._permute(rows, perm, inv)
        return jnp.sum(taken.astype(jnp.float32) * rows.astype(jnp.float32))

    def checkpointed(*names):
        """The layer's forward and backward with these names kept, jitted.
        The value is asked for with the gradients: without it nothing needs
        the first forward pass and XLA drops it."""
        # (A function of its own each time: JAX keeps a checkpointed
        # function's trace by the function.)
        kept = jax.checkpoint(
            lambda *a: layer(*a),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        return jax.jit(jax.value_and_grad(
            kept, argnums=(0, 1, 2, 3, 4), has_aux=True))

    as_a_block_runs_it = checkpointed(*gpt.SAVED_NAMES)
    if args.routing or args.first_products:
        by_what_is_kept = routing_rows if args.routing else first_product_rows
        line = json.dumps({
            "tokens": T, "embed": d, "width": m, "experts": E, "top_k": k,
            "router_width": wide, "rows": R, "skew": args.skew,
            "device_kind": device.device_kind,
            "layer_checkpointed_fwd_bwd_ms": by_what_is_kept(
                checkpointed, h, weights)})
        return emit(line)
    out = {"tokens": T, "embed": d, "width": m, "experts": E, "top_k": k,
           "router_width": wide, "first_expert": first, "rows": R,
           "rows_held": rows_held, "windows": -(-rows_held // R),
           "all_rows": args.all_rows,
           "skew": args.skew, "device_kind": device.device_kind,
           "busiest_over_mean": busiest,
           "layer_fwd_ms": timed(jax.jit(layer), h, *weights),
           "layer_fwd_bwd_ms": timed(jax.jit(jax.grad(
               layer, argnums=(0, 1, 2, 3, 4), has_aux=True)), h, *weights),
           "layer_checkpointed_fwd_bwd_ms": {
               "nothing": timed(checkpointed(), h, *weights),
               "SAVED_NAMES": timed(as_a_block_runs_it, h, *weights)},
           "experts_fwd_ms": timed(jax.jit(experts), rows_in, *weights[1:]),
           "experts_fwd_bwd_ms": timed(jax.jit(jax.grad(
               experts, argnums=(0, 1, 2, 3))), rows_in, *weights[1:]),
           "permute_fwd_ms": timed(jax.jit(permute), rows_in),
           "permute_fwd_bwd_ms": timed(jax.jit(jax.grad(permute)), rows_in)}
    # Least times of the grouped matmuls: 3 matrices, 2 ops a MAC, the rows
    # the experts draw.
    out["experts_fwd_least_ms"] = 1e3 * 3 * 2 * int(counts.sum()) * d * m \
        / 197e12
    if R < T * k:
        out["to_tokens_ms"] = to_tokens_rows(h, router, first, E, k, R)

    # Against every expert on every token, on what that can hold.
    n = min(T, 1024)
    with jax.default_matmul_precision("highest"):
        if E < wide or args.renormalize:
            from benchmarks.reference import gpt_linear_moe_dp as reference
            want, *_ = jax.jit(lambda h, *w: reference.expert_block(
                h, dict(zip(("router", "w_gate", "w_up", "w_down"), w)), k,
                first))(h[:n].astype(jnp.float32), *weights)
        else:
            from benchmarks.reference import gpt_moe_dp as reference
            want, *_ = jax.jit(lambda h, *w: reference.expert_layer(
                h, *w, k))(h[:n].astype(jnp.float32), *weights)
    got, _ = jax.jit(moe_layer)(h[:n], *weights)
    out["max_abs_error_over_max_abs"] = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        / jnp.max(jnp.abs(want)))
    if args.trace:
        from benchmarks import scope_reduce, trace_reduce
        from benchmarks.layer_metrics import moe_windows_per_step
        calls = 4
        log_dir = os.path.join(ROOT, "chiprun_out", "moe_layer_trace",
                               "skew" if args.skew else "even")
        with jax.profiler.trace(log_dir):
            for _ in range(calls):
                jax.block_until_ready(as_a_block_runs_it(h, *weights))
        path = trace_reduce.find_xplane(log_dir)
        taken = moe_windows_per_step.windows_taken(
            trace_reduce.first_device(trace_reduce.read_xplane(path, {})),
            scope_reduce.program_names(path))
        out["windows_traced"] = sum(taken.values()) / calls
    return emit(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
