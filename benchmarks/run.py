#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json, one process, one JSON line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This file knows no model and no metric by name. The cell names a
configuration and a traffic mix; the configuration's file names its job
(``jobs/<job>.py``, the user's side of a training run); each per-layer
metric is read by ``layer_metrics/<metric>.py``. README.md has the layout.

``--rehearsal`` runs the same control flow at the tiny sizes under
``tests/data/`` on a 4-device CPU mesh, says so on every line, and prints
no metric. Without it, anything but the TPU chips the cell asks for is an
error and no result is printed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARMUP_STEPS = 3
RING = 8            # host batches made from the seed; a step takes the next
TRACED_SEGMENTS = 2
REHEARSAL_DEVICES = 4


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def metrics_of(entries: list, cell: str) -> list:
    """The metrics a cell reports: those that list it, or list no cell."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def quartiles(values: list) -> tuple:
    if len(values) < 2:
        return values[0], values[0], values[0]
    q1, q2, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return q1, q2, q3


def every_chip_used(hvd, sharded_leaf, replicated_tree) -> bool:
    """The batch sits on every device of the mesh and the step's outputs are
    replicated on all of them (after ``chip_smoke.py``)."""
    import jax

    devices = set(hvd.mesh().devices.flat)
    if {s.device for s in sharded_leaf.addressable_shards} != devices:
        return False
    return all(leaf.sharding.is_fully_replicated
               and set(leaf.sharding.device_set) == devices
               for leaf in jax.tree.leaves(replicated_tree))


class Loop:
    """The training loop a user writes: place a fresh host batch, dispatch
    the step, and every ``log_every`` steps wait for that step's loss, as
    logging does. Each such segment is one throughput sample. The three
    calls are timed on the host's wall clock (``time.time_ns``): the
    profiler's trace says when it started on that clock, so the spans can be
    laid over the device's operations (``trace_reduce.read_xplane``)."""

    def __init__(self, hvd, job, state, ring, log_every: int):
        self.hvd, self.job, self.state, self.ring = hvd, job, state, ring
        self.log_every = log_every
        self.k = 0
        self.losses: list = []
        # name -> [(start, end)] in ns since the epoch
        self.spans: dict = {"place": [], "dispatch": [], "fence": []}
        self.rates: list = []           # samples/s, one per segment
        self.batch = None

    def _timed(self, name: str, fn):
        t0 = time.time_ns()
        out = fn()
        self.spans[name].append((t0, time.time_ns()))
        return out

    def take_spans(self) -> dict:
        """The spans so far; the loop starts anew."""
        taken, self.spans = self.spans, {k: [] for k in self.spans}
        return taken

    def place(self):
        """The next host batch of the ring, onto the mesh."""
        self.k += 1
        return self.hvd.shard_batch(self.ring[self.k % len(self.ring)])

    def step(self):
        self.batch = self._timed("place", self.place)
        *state, loss = self._timed(
            "dispatch", lambda: self.job.step(*self.state, self.batch))
        self.state = tuple(state)
        self.losses.append(loss)

    def segment(self):
        t0 = time.perf_counter()
        for _ in range(self.log_every):
            self.step()
        self._timed("fence", self.losses[-1].block_until_ready)
        self.rates.append(self.log_every * self.job.samples_per_step
                          / (time.perf_counter() - t0))


def profiler_options():
    """The device planes alone. With the host tracer on, at its default level
    (2) or at 1, the runtime's host-side layout change of a uint8 image batch
    writes a million ``Transpose`` events a batch and runs five to ten times
    slower (20 steps: a 627 MB trace, 85 s to stop it; my chip runs, PR 22):
    the chip then waits for a feed that no untraced run has. The loop's spans
    do not need it (``Loop``)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    return options


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)
    rehearsal = args.rehearsal
    prefix = "[rehearsal platform: cpu] " if rehearsal else ""

    def say(msg: str) -> None:
        print(prefix + msg, flush=True)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "config")
    data_root = os.path.join(HERE, "tests", "data") if rehearsal else HERE
    config = load_json(data_root, "configs",
                       os.path.basename(config_entry["file"]))
    traffic = load_json(data_root, "traffic", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    chips = REHEARSAL_DEVICES if rehearsal else cell["chips"]
    platform = "cpu" if rehearsal else "tpu"

    if rehearsal:
        # Asked for by name, before JAX is imported: never what a run falls
        # back to when it finds no chip.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    else:
        # The TPU first, so it is the default and JAX fails where there is
        # none; the host CPU beside it for a float32 reference.
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    sys.path.insert(0, ROOT)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"run.py: cell {cell['name']} seed {args.seed} seconds {seconds} "
        f"trace {args.trace} | platform: {device['platform']} device_kind: "
        f"{device['kind']} devices: {device['count']} | jax {jax.__version__}")
    if device["platform"] != platform or device["count"] != chips:
        raise SystemExit(
            f"run.py: cell {cell['name']} needs {chips} {platform} "
            f"device(s), found "
            f"{device['count']} of platform {device['platform']!r}; no result")
    peaks = load_json(HERE, "peaks.json")
    if not rehearsal and device["kind"] not in peaks:
        raise SystemExit(f"run.py: device kind {device['kind']!r} is not in "
                         "peaks.json; no result")
    # In a rehearsal any row will do: the readers run, their values are
    # withheld.
    peak = peaks[device["kind"]] if not rehearsal \
        else next(iter(peaks.values()))

    import horovod_tpu as hvd
    from benchmarks.context import RunContext, window_drift

    # Every program a run uses goes to the persistent cache, however quickly
    # it compiled: a second run of the cell then compiles nothing. The cache
    # is where hvd.init() puts it (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache).
    if not rehearsal:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    hvd.init()

    # ---- set-up: state from the seed, the check, the warm-up -------------
    marks = [("process start", T_START), ("chips and init",
                                          time.perf_counter())]

    def mark(name: str) -> None:
        marks.append((name, time.perf_counter()))

    job = importlib.import_module(f"benchmarks.jobs.{config['job']}").Job(
        config, traffic, args.seed)
    finish_check = job.check()
    mark("parameters and the check")
    loop = Loop(hvd, job, job.state(), job.host_batches(RING),
                traffic["log_every"])
    mark("state and host batches")
    t0 = time.perf_counter()
    loop.step()
    loop.losses[-1].block_until_ready()
    # A check that goes through the step itself has made its first call.
    first_step_s = job.first_call_s or time.perf_counter() - t0
    for _ in range(WARMUP_STEPS - 1):
        loop.step()
    loop.losses[-1].block_until_ready()
    warm = len(loop.losses)
    loop.take_spans()
    mark("warm-up")
    # A job may have left its reference running beside the warm-up.
    compared = {}
    for what, got, want, rtol in finish_check():
        err = abs(got - want) / abs(want)
        compared[what] = {"off_by": err, "limit": rtol}
        say(f"check: {what}: program {got:.6g} reference {want:.6g} "
            f"(rel {err:.2e}, allowed {rtol}) "
            f"{'ok' if err <= rtol else 'FAILED'}")
    mark("waiting for the reference")
    setup_s = time.perf_counter() - T_START
    if not rehearsal:       # a CPU's seconds are nobody's set-up time
        say(f"set-up {setup_s:.2f} s: " + ", ".join(
            f"{name} {t - marks[i][1]:.2f}"
            for i, (name, t) in enumerate(marks[1:]))
            + f"; in that, the first call of the step {first_step_s:.2f}")

    # ---- the window ------------------------------------------------------
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        loop.segment()
    window_s = time.perf_counter() - t0
    spans = loop.take_spans()
    window_rates = tuple(loop.rates)
    q1, rate, q3 = quartiles(window_rates)
    per_chip = rate / chips
    drift = window_drift(window_rates)
    say(f"window {window_s:.2f} s: {len(window_rates)} segments of "
        f"{loop.log_every} steps" + ("" if rehearsal else
        f"; {job.sample}/s/chip median {per_chip:.2f} quartiles "
        f"{q1 / chips:.2f} {q3 / chips:.2f} (spread {(q3 - q1) / rate:.4%})"
        + ("" if drift is None else
           f"; last third over first third of the segments {drift:+.4%}")))

    # ---- the traced stretch: the same loop, the profiler on --------------
    trace, steps_traced = None, 0
    if args.trace:
        from benchmarks import trace_reduce

        log_dir = os.path.join(ROOT, "chiprun_out", "trace", cell["name"])
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir,
                                 profiler_options=profiler_options())
        try:
            for _ in range(TRACED_SEGMENTS):
                loop.segment()
        finally:
            jax.profiler.stop_trace()
        steps_traced = TRACED_SEGMENTS * loop.log_every
        trace = trace_reduce.read_xplane(
            trace_reduce.find_xplane(log_dir), loop.take_spans())
        say(f"trace: {steps_traced} steps, device planes "
            f"{sorted(trace.devices)}" + ("" if rehearsal else
            "; the traced segments ran at " + " ".join(
                f"{r / rate:.4f}" for r in loop.rates[-TRACED_SEGMENTS:])
            + " of the window's median rate"))

    # ---- what the run showed ---------------------------------------------
    losses = [float(v) for v in jax.device_get(loop.losses)]
    attempted = len(losses) - warm
    failed = sum(not math.isfinite(v) for v in losses[warm:])
    compiles = job.step._cache_size()
    placed = every_chip_used(hvd, jax.tree.leaves(loop.batch)[0],
                             (loop.state, loop.losses[-1]))
    # What ``correct`` rests on beside the check's rows, each as a count
    # that has to be nought (warm-up and traced steps count too).
    compared["losses not finite"] = {
        "off_by": sum(not math.isfinite(v) for v in losses), "limit": 0}
    compared["step executables beyond one"] = {"off_by": compiles - 1,
                                               "limit": 0}
    compared["chips not all used"] = {"off_by": int(not placed), "limit": 0}
    correct = all(row["off_by"] <= row["limit"] for row in compared.values())
    say(f"steps {attempted} in the window, {failed} not finite; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; step executables {compiles}; "
        f"every chip used: {placed}; correct: {correct}")
    result = {"correct": correct, "attempted": attempted, "failed": failed}

    # What a chip held at most: the allocator's peak of live buffers plus the
    # scratch the runtime reserves for the programs it has loaded, which the
    # allocator does not count (0.69 GiB against 8.47 GiB reserved for the
    # ResNet step, my chip run, PR 22). The CPU backend reports neither.
    stats = [] if rehearsal else [d.memory_stats() for d in devices]
    memory_peak = max((m["peak_bytes_in_use"] + m["peak_bytes_reserved"]
                       for m in stats), default=0)
    live_peak = max((m["peak_bytes_in_use"] for m in stats), default=0)
    device["memory_peak_bytes"] = memory_peak
    # The compiler's own account of the step, beside the runtime's. Lowering
    # again costs nothing: the trace and the executable are cached.
    analysis = job.step.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (*loop.state, loop.batch))).compile().memory_analysis()
    say(f"memory on the fullest chip: {memory_peak / 2**30:.3f} GiB = "
        f"peak_bytes_in_use {live_peak / 2**30:.3f} + peak_bytes_reserved "
        f"{(memory_peak - live_peak) / 2**30:.3f}; memory_analysis() of the "
        f"step: arguments {analysis.argument_size_in_bytes / 2**30:.3f}, "
        f"outputs {analysis.output_size_in_bytes / 2**30:.3f} (aliased "
        f"{analysis.alias_size_in_bytes / 2**30:.3f}), temporaries "
        f"{analysis.temp_size_in_bytes / 2**30:.3f} GiB")
    wanted = metrics_of(bench["end_to_end"], cell["name"])
    values = {job.throughput_metric: per_chip, "setup_s": setup_s}
    if args.trace:
        ctx = RunContext(
            job=job, chips=chips, peak=peak, throughput=rate, spans=spans,
            first_step_s=first_step_s, step_compiles=compiles,
            memory_peak_bytes=memory_peak, trace=trace,
            steps_traced=steps_traced, rates=window_rates)
        wanted = metrics_of(bench["per_layer"], cell["name"])
        values = {m["name"]: importlib.import_module(
            f"benchmarks.layer_metrics.{m['name']}").read(ctx)
            for m in wanted}
        if trace.devices:
            busy_s, traced_s = trace_reduce.busy_seconds(trace)
            device.update(busy_s=busy_s, window_s=traced_s)
            result["breakdown"] = trace_reduce.breakdown(trace)
            say(f"device busy {busy_s:.4f} s of the {traced_s:.4f} s the "
                f"{steps_traced} traced steps took, feed included (idle "
                f"{1 - busy_s / traced_s:.2%}; by segment, chip 0: "
                + " ".join(f"{x:.2%}" for x in
                           trace_reduce.idle_by_segment(trace)) + ")")
            for kernel in job.kernel_costs:
                say(f"{kernel} roofline share (%) and its bound: "
                    f"{ctx.kernel_roofline(kernel)}")
    hvd.shutdown()
    if rehearsal:
        # The readers ran; what they read on a CPU is not a device number.
        say("metrics read (values withheld): " + " ".join(
            m["name"] for m in wanted if values.get(m["name"]) is not None))
        result.update(rehearsal=True, device={
            k: device[k] for k in ("platform", "kind", "count")})
    else:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if values.get(m["name"]) is not None}
        result["device"] = device
    # Every number ``correct`` rests on, beside its limit: the end of
    # standard error and the last key of the line, which is what a record
    # keeps of a run that was not correct. A number that is not finite goes
    # into the line as its name: JSON has none for it.
    for what, row in compared.items():
        print(f"{prefix}compared: {what}: off by {row['off_by']:.3e}, limit "
              f"{row['limit']:g}", file=sys.stderr, flush=True)
        if not math.isfinite(row["off_by"]):
            row["off_by"] = repr(row["off_by"])
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
