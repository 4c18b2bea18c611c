#!/usr/bin/env python3
"""A cell's correctness check over many seeds, in one process on the chip.

    chiprun --chips 1 -- python scripts/check_sweep.py --workload <cell> --seeds 10

For each seed it builds the cell's job as ``benchmarks/run.py`` does, runs
``check()`` and prints one JSON line with every row's relative error (and
what else the job kept of the check: tokens per expert, choices moved); the
last line gives each row's largest error. This is where the tolerances at the
top of a ``benchmarks/jobs/*.py`` come from. Lines are appended to
``chiprun_out/check_sweep.jsonl``.

``--variant NAME`` runs the same check on a program with one of its
mechanisms left out, on the shipped program's parameters (``VARIANTS``: the
band ignored, the selection bias out
of the choice or never updated, the weights' constant, the norms after the branches or the
shared expert dropped, the router's product in one bfloat16 pass), against
the untouched reference: what a job's tolerances must catch. A variant
changes the job's ``GPTConfig`` after the job is built, before its step is
traced; a job without the field it needs fails by name.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _router_in_bfloat16():
    """The expert layer with the router's float32 product at the backend's
    default precision (on the TPU one bfloat16 pass of the MXU) where the
    program asks for the highest: ``parallel/moe.py`` sees a ``jax.numpy``
    whose ``dot`` takes no notice of ``precision``."""
    import jax.numpy as jnp
    from jax import lax
    from horovod_tpu.parallel import moe

    class OnePass:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def dot(a, b, precision=None, **kw):
            return jnp.dot(a, b, precision=lax.Precision.DEFAULT, **kw)

    moe.jnp = OnePass()


# name -> what it does to a job already built (its step not yet traced)
VARIANTS = {
    "full_causal": lambda job: _replace(job, layers=tuple(
        dataclasses.replace(spec, window=None) for spec in job.cfg.plan)),
    "no_bias": lambda job: _replace(job, router_bias=False),
    "no_bias_update": lambda job: setattr(job, "bias_rate", 0.0),
    "no_route_scale": lambda job: _replace(job, route_scale=1.0),
    "no_post_norm": lambda job: _replace(job, post_norm=False),
    "no_shared": lambda job: _replace(job, shared_expert_dim=0),
    "router_bf16": lambda job: _router_in_bfloat16(),
}


def _replace(job, **fields) -> None:
    job.cfg = dataclasses.replace(job.cfg, **fields)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=10)
    parser.add_argument("--first-seed", type=int, default=2147484000)
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--variant", choices=sorted(VARIANTS))
    args = parser.parse_args()
    from benchmarks import run

    bench = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.find(bench["workloads"], args.workload, "workload")
    data = os.path.join(run.HERE, "tests", "data") if args.rehearsal \
        else run.HERE
    config = run.load_json(data, "configs", os.path.basename(
        run.find(bench["configs"], cell["config"], "config")["file"]))
    traffic = run.load_json(data, "traffic", cell["traffic"] + ".json")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + (
            f" --xla_force_host_platform_device_count={cell['chips']}")
    else:
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    import jax
    import numpy as np

    import horovod_tpu as hvd

    print(f"platform: {jax.devices()[0].platform} device_kind: "
          f"{jax.devices()[0].device_kind} devices: {len(jax.devices())}",
          flush=True)
    hvd.init()
    jobs = importlib.import_module(f"benchmarks.jobs.{config['job']}")
    worst: dict = {}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_sweep.jsonl"),
              "a") as out:
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            job = jobs.Job(config, traffic, seed)
            if args.variant:
                job.state()         # the shipped program's, made before
                VARIANTS[args.variant](job)
            line = {"workload": args.workload, "seed": seed,
                    "rehearsal": args.rehearsal, "variant": args.variant}
            for what, got, want, rtol in job.check()():
                err = abs(got - want) / abs(want)
                line[what] = {"program": got, "reference": want, "rel": err,
                              "allowed": rtol}
                worst[what] = max(worst.get(what, 0.0), err)
            counts = getattr(job, "expert_counts", None)
            if counts is not None:
                line["busiest_over_mean"] = float(
                    (counts.max(-1) / counts.mean(-1)).max())
                line["choices_moved"] = job.choices_moved
                line["choices"] = int(np.sum(counts))
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            # The next seed's state needs the room this one's holds.
            del job
            for array in jax.live_arrays():
                array.delete()
        last = {"workload": args.workload, "seeds": args.seeds,
                "variant": args.variant, "largest_rel": worst}
        print(json.dumps(last), flush=True)
        out.write(json.dumps(last) + "\n")
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
