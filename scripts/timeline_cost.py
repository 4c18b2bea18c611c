#!/usr/bin/env python3
"""What ``hvd.start_timeline`` costs while it runs: one cell of the benchmark,
the benchmark's own loop, segments without a timeline, with one, and without
again.

    chiprun --chips 1 -- python3 scripts/timeline_cost.py --workload resnet50_dp1

Prints one JSON line: the segments' rates (samples/s/chip) in the three
phases, the timeline's rate over the mean of the plain ones, the seconds
``stop_timeline`` took, the sizes of the ``.xplane.pb`` and of the span file,
and what the spans say about the feed: each ``shard_batch`` call and how long
after its return the batch was ready on every chip. The files stay under
``chiprun_out/timeline/<cell>/``. ``--rehearsal`` runs the same control flow
at the tiny sizes on a 4-device CPU mesh and prints no rate.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# Segments of the benchmark's loop (``log_every`` steps and a fence each):
# without a timeline before and after, and with one (20 ResNet-50 steps).
PLAIN_SEGMENTS = 6
TIMELINE_SEGMENTS = 2


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()

    from benchmarks import run as bench

    spec = bench.load_json(ROOT, "BENCHMARK.json")
    cell = bench.find(spec["workloads"], args.workload, "workload")
    config_entry = bench.find(spec["configs"], cell["config"], "config")
    data_root = os.path.join(bench.HERE, "tests", "data") \
        if args.rehearsal else bench.HERE
    config = bench.load_json(data_root, "configs",
                             os.path.basename(config_entry["file"]))
    traffic = bench.load_json(data_root, "traffic", cell["traffic"] + ".json")
    chips = bench.REHEARSAL_DEVICES if args.rehearsal else cell["chips"]
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    else:
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    devices = jax.devices()
    platform = "cpu" if args.rehearsal else "tpu"
    if devices[0].platform != platform or len(devices) != chips:
        raise SystemExit(f"timeline_cost.py: {args.workload} needs {chips} "
                         f"{platform} device(s), found {len(devices)} of "
                         f"platform {devices[0].platform!r}; no result")

    import horovod_tpu as hvd

    hvd.init()
    job = importlib.import_module(f"benchmarks.jobs.{config['job']}").Job(
        config, traffic, args.seed)
    finish_check = job.check()
    loop = bench.Loop(hvd, job, job.state(), job.host_batches(bench.RING),
                      traffic["log_every"])
    for _ in range(bench.WARMUP_STEPS):
        loop.step()
    loop.losses[-1].block_until_ready()
    finish_check()

    def phase(segments: int) -> list:
        start = len(loop.rates)
        for _ in range(segments):
            loop.segment()
        return [r / chips for r in loop.rates[start:]]

    out_dir = os.path.join(ROOT, "chiprun_out", "timeline", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    path = os.path.join(out_dir, "timeline.json")
    before = phase(PLAIN_SEGMENTS)
    hvd.start_timeline(path)
    traced = phase(TIMELINE_SEGMENTS)
    t0 = time.perf_counter()
    hvd.stop_timeline()
    stop_s = time.perf_counter() - t0
    after = phase(PLAIN_SEGMENTS)

    with open(path) as f:
        doc = json.load(f)
    spans: dict = {}
    for e in doc["traceEvents"]:
        spans.setdefault(e["name"], []).append(e["dur"] / 1e3)     # ms
    xplane = doc["metadata"]["xplane"]
    plain = statistics.mean(before + after)
    result = {
        "workload": cell["name"],
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "steps_in_timeline": TIMELINE_SEGMENTS * loop.log_every,
        "stop_timeline_s": stop_s,
        "xplane_bytes": os.path.getsize(xplane) if xplane else None,
        "span_file_bytes": os.path.getsize(path),
        "spans": {name: len(ms) for name, ms in spans.items()},
        "shard_batch_ms": sorted(spans.get("shard_batch", [])),
        "batch_ready_ms": sorted(spans.get("batch_ready", [])),
    }
    if args.rehearsal:
        result["rehearsal"] = True
    else:
        result.update(
            rate_unit=f"{job.sample}/s/chip", plain_before=before,
            timeline=traced, plain_after=after,
            timeline_over_plain=statistics.mean(traced) / plain)
    hvd.shutdown()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
