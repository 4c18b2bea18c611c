#!/usr/bin/env python3
"""Time the placement of one host batch leaf alone, after a fence: how long
the call takes and how long after its return the leaf is on every chip.

    chiprun --chips 1 -- python3 scripts/place_time.py --shape 256,224,224,3 --dtype uint8
    chiprun --chips 4 -- python3 scripts/place_time.py --shape 1024,224,224,3 --dtype uint8 --form flat

The leaf is sharded on dimension 0 over ``hvd.init()``'s mesh, as
``hvd.shard_batch`` shards it. ``--form`` (several, default all four) says how
it crosses:

- ``direct``: ``jax.device_put(x, sharding)``. The runtime makes the device
  layout of the leaf's own shape on the host before a byte crosses (a uint8
  NHWC batch on a v5e lies ``{0,2,3,1:T(8,128)(4,1)}``: a whole transposition).
- ``flat``: the ``[N, rest]`` view of the same bytes is placed, and a jitted
  reshape (the flat array donated) gives the leaf its shape on the device.
- ``flat32``: the same bytes viewed as ``uint32 [N, rest_bytes / 4]``, turned
  back on the device by ``lax.bitcast_convert_type`` and the reshape.
- ``shipped``: ``hvd.shard_batch`` itself under ``hvd.start_timeline``; the
  two numbers are its ``shard_batch`` and ``batch_ready`` spans.

Every repeat starts with nothing queued on the chips (the state after a
fence) and takes the next host array of a ring of ``--ring`` made from
``--seed``. One JSON line a form: ``shard_batch_ms`` (call to return) and
``batch_ready_ms`` (return to ready on every chip), median and quartiles,
host clock; ``restore_ms`` the device operation alone (``--ring`` flat arrays
already on the chips, restored back to back, the time over their number);
``equal`` whether the first placed leaf holds the host's bytes in the
shape, dtype and sharding ``direct`` gives. Lines are also appended to
``chiprun_out/place_time.jsonl``. ``--rehearsal`` runs the same control flow
on a 4-device CPU mesh and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
FORMS = ("direct", "flat", "flat32", "shipped")
REHEARSAL_DEVICES = 4


def quartiles(ms: list) -> dict:
    q1, q2, q3 = statistics.quantiles(ms, n=4)
    return {"median": q2, "q1": q1, "q3": q3, "min": min(ms), "max": max(ms)}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", default="256,224,224,3")
    parser.add_argument("--dtype", default="uint8")
    parser.add_argument("--form", nargs="+", choices=FORMS, default=FORMS)
    parser.add_argument("--repeats", type=int, default=24)
    parser.add_argument("--ring", type=int, default=8)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={REHEARSAL_DEVICES}")

    import jax
    import numpy as np
    from jax import lax

    import horovod_tpu as hvd

    platform = jax.devices()[0].platform
    if (platform == "cpu") != args.rehearsal:
        raise SystemExit(f"place_time.py: platform {platform!r}; times come "
                         "from a chip, --rehearsal from the CPU; no result")
    hvd.init()
    shape = tuple(int(n) for n in args.shape.split(","))
    dtype = np.dtype(args.dtype)
    sharding = jax.sharding.NamedSharding(hvd.mesh(), hvd.batch_spec())
    rng = np.random.default_rng(args.seed)
    size = int(np.prod(shape)) * dtype.itemsize
    ring = [rng.integers(0, 256, (size,), dtype=np.uint8).view(dtype)
            .reshape(shape) for _ in range(args.ring)]
    row_bytes = size // shape[0]

    restore = jax.jit(lambda f: f.reshape(shape), out_shardings=sharding,
                      donate_argnums=0)
    restore32 = jax.jit(
        lambda f: lax.bitcast_convert_type(f, dtype).reshape(shape),
        out_shardings=sharding)     # a uint32 buffer cannot be donated to it

    def flat(x):
        return jax.device_put(x.reshape(shape[0], -1), sharding)

    def flat32(x):
        return jax.device_put(
            x.reshape(shape[0], -1).view(np.uint8).view(np.uint32), sharding)

    forms = {
        "direct": (lambda x: jax.device_put(x, sharding), None, None),
        "flat": (lambda x: restore(flat(x)), flat, restore),
        "flat32": (lambda x: restore32(flat32(x)), flat32, restore32),
        "shipped": (hvd.shard_batch, None, None),
    }
    device = {"platform": platform, "kind": jax.devices()[0].device_kind,
              "count": jax.device_count()}
    out_path = os.path.join(ROOT, "chiprun_out", "place_time.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for name in args.form:
        place, to_device, on_device = forms[name]
        line = {"form": name, "shape": list(shape), "dtype": dtype.name,
                "leaf_bytes": size, "device": device,
                "repeats": args.repeats}
        if name == "flat32" and (row_bytes % 4 or dtype.itemsize != 1):
            print(json.dumps(dict(line, skipped="a row of one-byte elements "
                                  "that divides by four, or no uint32 view")),
                  flush=True)
            continue
        want = jax.device_put(ring[0], sharding)
        got = place(ring[0])        # compiles what the form compiles
        line["equal"] = bool(
            got.shape == want.shape and got.dtype == want.dtype
            and got.sharding == want.sharding
            and np.asarray(got).tobytes() == ring[0].tobytes())
        del want, got
        calls, readies = [], []
        timeline = None
        if name == "shipped":
            timeline = os.path.join(os.path.dirname(out_path), "place_time",
                                    "timeline.json")
            os.makedirs(os.path.dirname(timeline), exist_ok=True)
            hvd.start_timeline(timeline)
        for k in range(args.repeats + 2):       # two warm ones
            x = ring[k % len(ring)]
            t0 = time.perf_counter()
            placed = place(x)
            t1 = time.perf_counter()
            jax.block_until_ready(placed)       # the next starts after a fence
            t2 = time.perf_counter()
            del placed
            if k >= 2:
                calls.append(1e3 * (t1 - t0))
                readies.append(1e3 * (t2 - t1))
        if timeline is not None:
            hvd.stop_timeline()
            with open(timeline) as f:
                events = json.load(f)["traceEvents"]
            calls, readies = ([e["dur"] / 1e3 for e in events
                               if e["name"] == span][2:]
                              for span in ("shard_batch", "batch_ready"))
            line["leaves"] = {
                s[1]["path"]: s[2] for s in hvd.metrics()
                ["hvdtpu_spmd_shard_batch_leaves_total"]["samples"]}
        if on_device is not None:
            for _ in range(2):      # the second is the reading
                staged = [to_device(x) for x in ring]
                jax.block_until_ready(staged)
                t0 = time.perf_counter()
                restored = [on_device(f) for f in staged]
                jax.block_until_ready(restored)
                restore_ms = 1e3 * (time.perf_counter() - t0) / len(ring)
                del staged, restored
            if not args.rehearsal:
                line["restore_ms"] = restore_ms
        if args.rehearsal:
            line["rehearsal"] = True
        else:
            line.update(shard_batch_ms=quartiles(calls),
                        batch_ready_ms=quartiles(readies))
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
