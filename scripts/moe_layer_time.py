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
lower index).
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


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--embed", type=int, default=2048)
    parser.add_argument("--width", type=int, default=1024)
    parser.add_argument("--experts", type=int, default=64)
    parser.add_argument("--top-k", type=int, default=8)
    parser.add_argument("--skew", action="store_true")
    args = parser.parse_args()
    T, d, m, E, k = (args.tokens, args.embed, args.width, args.experts,
                     args.top_k)
    device = jax.devices()[0]
    print(f"platform: {device.platform} device_kind: {device.device_kind}",
          flush=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    h = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
    router = jax.random.normal(ks[1], (d, E), jnp.float32) / d ** 0.5
    if args.skew:
        router = jnp.broadcast_to(router[:, :1], router.shape)
    w_gate = jax.random.normal(ks[2], (E, d, m), jnp.float32) / d ** 0.5
    w_up = jax.random.normal(ks[3], (E, d, m), jnp.float32) / d ** 0.5
    w_down = jax.random.normal(ks[4], (E, m, d), jnp.float32) / m ** 0.5
    weights = (router, w_gate, w_up, w_down)

    def layer(h, *w):
        y, aux = moe.moe_layer(h, *w, top_k=k)
        return jnp.sum(y.astype(jnp.float32)) + aux["load_balance"] \
            + aux["router_z"], aux["counts"]

    rows_in = jax.random.normal(ks[5], (T * k, d), jnp.bfloat16)
    _, counts = jax.jit(layer)(h, *weights)
    perm = jax.random.permutation(ks[5], T * k)
    inv = jnp.argsort(perm)

    def experts(rows, w_gate, w_up, w_down):
        def gmm(lhs, w):
            return lax.ragged_dot(lhs, w.astype(jnp.bfloat16), counts)
        hidden = jax.nn.silu(gmm(rows, w_gate)) * gmm(rows, w_up)
        return jnp.sum(gmm(hidden, w_down).astype(jnp.float32))

    def permute(rows):
        return jnp.sum(moe._permute(rows, perm, inv).astype(jnp.float32)
                       * rows.astype(jnp.float32))

    def checkpointed(*names):
        """ms of the layer's forward and backward with these names kept.
        The value is asked for with the gradients: without it nothing needs
        the first forward pass and XLA drops it."""
        kept = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.save_only_these_names(
                *names))
        return timed(jax.jit(jax.value_and_grad(
            kept, argnums=(0, 1, 2, 3, 4), has_aux=True)), h, *weights)

    out = {"tokens": T, "embed": d, "width": m, "experts": E, "top_k": k,
           "skew": args.skew, "device_kind": device.device_kind,
           "busiest_over_mean": float(counts.max() * E / counts.sum()),
           "layer_fwd_ms": timed(jax.jit(layer), h, *weights),
           "layer_fwd_bwd_ms": timed(jax.jit(jax.grad(
               layer, argnums=(0, 1, 2, 3, 4), has_aux=True)), h, *weights),
           "layer_checkpointed_fwd_bwd_ms": {
               "nothing": checkpointed(),
               "SAVED_NAMES": checkpointed(*gpt.SAVED_NAMES)},
           "experts_fwd_ms": timed(jax.jit(experts), rows_in, *weights[1:]),
           "experts_fwd_bwd_ms": timed(jax.jit(jax.grad(
               experts, argnums=(0, 1, 2, 3))), rows_in, *weights[1:]),
           "permute_fwd_ms": timed(jax.jit(permute), rows_in),
           "permute_fwd_bwd_ms": timed(jax.jit(jax.grad(permute)), rows_in)}
    # Least times of the grouped matmuls: 3 matrices, 2 ops a MAC, T k rows.
    out["experts_fwd_least_ms"] = 1e3 * 3 * 2 * T * k * d * m / 197e12

    # Against every expert on every token, on what that can hold.
    from benchmarks.reference import gpt_moe_dp as reference
    n = min(T, 1024)
    with jax.default_matmul_precision("highest"):
        want, *_ = jax.jit(lambda h, *w: reference.expert_layer(
            h, *w, k))(h[:n].astype(jnp.float32), *weights)
    got, _ = jax.jit(lambda h, *w: moe.moe_layer(h, *w, top_k=k))(
        h[:n], *weights)
    out["max_abs_error_over_max_abs"] = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        / jnp.max(jnp.abs(want)))
    line = json.dumps(out)
    print(line, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_layer_time.jsonl"),
              "a") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
