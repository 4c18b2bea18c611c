#!/usr/bin/env python3
"""A cell's correctness check over many seeds, in one process on the chip.

    chiprun --chips 1 -- python scripts/check_sweep.py --workload <cell> --seeds 10

For each seed it builds the cell's job as ``benchmarks/run.py`` does, runs
``check()`` and prints one JSON line with every row's relative error (and
what else the job kept of the check: tokens per expert, choices moved); the
last line gives each row's largest error. This is where the tolerances at the
top of a ``benchmarks/jobs/*.py`` come from. Lines are appended to
``chiprun_out/check_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=10)
    parser.add_argument("--first-seed", type=int, default=2147484000)
    parser.add_argument("--rehearsal", action="store_true")
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
            line = {"workload": args.workload, "seed": seed,
                    "rehearsal": args.rehearsal}
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
                "largest_rel": worst}
        print(json.dumps(last), flush=True)
        out.write(json.dumps(last) + "\n")
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
