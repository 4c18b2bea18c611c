"""Environment-variable knobs for horovod_tpu.

The reference parses ~40 ``HOROVOD_*`` env vars in C++
(``horovod/common/utils/env_parser.cc``, names in ``horovod/common/common.h:68-108``).
We mirror that config surface under the ``HVDTPU_*`` prefix, parsed in Python (and in
the native core where relevant). Every knob the reference exposes that still makes
sense on TPU has an equivalent here.
"""

from __future__ import annotations

import os
from typing import Optional

# ---------------------------------------------------------------------------
# Knob names (reference: horovod/common/common.h:68-108)
# ---------------------------------------------------------------------------

# Topology / rendezvous (reference: HOROVOD_RANK/SIZE/LOCAL_RANK/... set by the
# gloo_run launcher, horovod/runner/gloo_run.py:70-95)
HVDTPU_RANK = "HVDTPU_RANK"
HVDTPU_SIZE = "HVDTPU_SIZE"
HVDTPU_LOCAL_RANK = "HVDTPU_LOCAL_RANK"
HVDTPU_LOCAL_SIZE = "HVDTPU_LOCAL_SIZE"
HVDTPU_CROSS_RANK = "HVDTPU_CROSS_RANK"
HVDTPU_CROSS_SIZE = "HVDTPU_CROSS_SIZE"
HVDTPU_HOSTNAME = "HVDTPU_HOSTNAME"
HVDTPU_SECRET = "HVDTPU_SECRET"  # shared job secret (reference: secret.py)
# Multi-NIC escape hatch: the address this process advertises to peers
# (reference analog: driver_service.py NIC intersection).
HVDTPU_ADVERTISE_ADDR = "HVDTPU_ADVERTISE_ADDR"
# Multi-host SPMD bootstrap (jax.distributed; the MPI_Init/gloo-rendezvous
# role for the compiled path — SURVEY §2.7 control plane).
HVDTPU_COORDINATOR_ADDR = "HVDTPU_COORDINATOR_ADDR"
HVDTPU_NUM_PROCESSES = "HVDTPU_NUM_PROCESSES"
HVDTPU_PROCESS_ID = "HVDTPU_PROCESS_ID"
HVDTPU_AUTO_DISTRIBUTED = "HVDTPU_AUTO_DISTRIBUTED"
HVDTPU_RENDEZVOUS_ADDR = "HVDTPU_RENDEZVOUS_ADDR"
HVDTPU_RENDEZVOUS_PORT = "HVDTPU_RENDEZVOUS_PORT"
HVDTPU_CONTROLLER_ADDR = "HVDTPU_CONTROLLER_ADDR"
HVDTPU_CONTROLLER_PORT = "HVDTPU_CONTROLLER_PORT"

# Background-loop / fusion tuning (reference: HOROVOD_FUSION_THRESHOLD,
# HOROVOD_CYCLE_TIME — horovod/common/operations.cc:456-472)
HVDTPU_FUSION_THRESHOLD = "HVDTPU_FUSION_THRESHOLD"
HVDTPU_CYCLE_TIME = "HVDTPU_CYCLE_TIME"

# Native allreduce algorithm selection (reference fork: the IST-DASLab
# ring/scatter-allgather/parameter-server/tree menu; native/data_plane.h
# AllreduceAlgo). ALGO: auto | ring | recursive_doubling | tree |
# scatter_allgather | parameter_server. CROSSOVER: AUTO's ring/latency
# switchover in bytes (also autotuned). SEGMENT_BYTES: ring pipeline
# segment granularity. SA_GROUP: group-size floor at which AUTO's
# big-message dispatch prefers scatter-allgather over the ring (default 16;
# 0 removes scatter-allgather from the AUTO menu).
HVDTPU_ALLREDUCE_ALGO = "HVDTPU_ALLREDUCE_ALGO"
HVDTPU_ALLREDUCE_CROSSOVER = "HVDTPU_ALLREDUCE_CROSSOVER"
HVDTPU_ALLREDUCE_SEGMENT_BYTES = "HVDTPU_ALLREDUCE_SEGMENT_BYTES"
HVDTPU_ALLREDUCE_SA_GROUP = "HVDTPU_ALLREDUCE_SA_GROUP"

# Valid HVDTPU_ALLREDUCE_ALGO values, mapped to hvdtpu::AllreduceAlgo.
ALLREDUCE_ALGOS = ("auto", "ring", "recursive_doubling", "tree",
                   "scatter_allgather", "parameter_server")

# Control-plane frame batching (native/core.cpp CtrlOutbox): "1" (default)
# coalesces each background cycle's per-tensor READY/RESPONSES/CLOCK/
# GRADCHECK frames into one vectored send per peer — one syscall per peer
# per cycle instead of one per message; "0" restores frame-per-send.
HVDTPU_CTRL_BATCH = "HVDTPU_CTRL_BATCH"

# Broadcast schedule floor (native/data_plane.h, docs/collectives.md
# "Broadcast & alltoall"): payloads at or below this many bytes ride the
# flat root-fanout schedule (one hop of latency), larger ones the binomial
# tree (⌈log2 n⌉ depth). Default 4096; unset/-1 keeps the native default.
HVDTPU_BCAST_FLAT_MAX = "HVDTPU_BCAST_FLAT_MAX"

# Transport subsystem (native/transport.h + shm_transport.h; reference
# analog: the fork's MPI / NCCL / CUDA-IPC SHM / P2P communicator menu).
# SHM: "1" (default) lets same-host rank pairs negotiate POSIX
# shared-memory ring lanes at rendezvous, "0" forces TCP everywhere.
# SHM_RING_BYTES: per-direction ring capacity (default 1 MB).
HVDTPU_SHM = "HVDTPU_SHM"
HVDTPU_SHM_RING_BYTES = "HVDTPU_SHM_RING_BYTES"
# Hierarchical two-level allreduce (native/data_plane.h HierMode): intra-host
# reduce-scatter/allgather over shm lanes + one leader per host on the flat
# TCP algorithm. "auto" (default) leaves the switch to the Bayesian
# autotuner; "1"/"0" force it.
HVDTPU_ALLREDUCE_HIER = "HVDTPU_ALLREDUCE_HIER"

# Valid HVDTPU_ALLREDUCE_HIER values, mapped to hvdtpu::HierMode.
ALLREDUCE_HIER_MODES = {"0": 0, "off": 0, "false": 0,
                        "1": 1, "on": 1, "true": 1,
                        "auto": 2, "": 2}

# Zero-copy transport lane (native/transport.h ZeroCopySender +
# shm_transport.h; docs/collectives.md "Zero-copy TCP lane"). TCP_ZEROCOPY:
# "auto" (default) probes SO_ZEROCOPY per lane at Connect and backs off to
# the copy path when the kernel reports it copied anyway (loopback); "on"
# keeps a successful probe armed; "off" never probes; "uring" probes an
# io_uring submission ring first (SEND_ZC where the kernel has it) and
# falls down the same ladder. SHM_NUMA: NUMA placement of the shm rings —
# each side pins its inbound ring to its own node ("auto": only on
# multi-node hosts, probed via /sys/devices/system/node). DOORBELL_BATCH:
# futex-doorbell coalescing window in bytes (0 = built-in default, 1 =
# wake on every cursor advance — the pre-PR-9 behavior).
HVDTPU_TCP_ZEROCOPY = "HVDTPU_TCP_ZEROCOPY"
HVDTPU_SHM_NUMA = "HVDTPU_SHM_NUMA"
HVDTPU_DOORBELL_BATCH = "HVDTPU_DOORBELL_BATCH"

# Valid HVDTPU_TCP_ZEROCOPY values, mapped to hvdtpu::ZeroCopyMode.
TCP_ZEROCOPY_MODES = {"auto": 0, "on": 1, "off": 2, "uring": 3}

# Valid HVDTPU_SHM_NUMA values, mapped to hvdtpu::ShmNumaMode.
SHM_NUMA_MODES = {"auto": 0, "on": 1, "off": 2}

# Response cache (reference: HOROVOD_CACHE_CAPACITY)
HVDTPU_CACHE_CAPACITY = "HVDTPU_CACHE_CAPACITY"

# Stall inspector (reference: HOROVOD_STALL_CHECK_DISABLE, ..._TIME_SECONDS,
# ..._SHUTDOWN_TIME_SECONDS — horovod/common/stall_inspector.cc)
HVDTPU_STALL_CHECK_DISABLE = "HVDTPU_STALL_CHECK_DISABLE"
HVDTPU_STALL_CHECK_TIME_SECONDS = "HVDTPU_STALL_CHECK_TIME_SECONDS"
HVDTPU_STALL_SHUTDOWN_TIME_SECONDS = "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS"

# Timeline (reference: HOROVOD_TIMELINE, HOROVOD_TIMELINE_MARK_CYCLES —
# horovod/common/operations.cc:437-454)
HVDTPU_TIMELINE = "HVDTPU_TIMELINE"
HVDTPU_TIMELINE_MARK_CYCLES = "HVDTPU_TIMELINE_MARK_CYCLES"

# Cross-rank distributed tracing (docs/tracing.md; no reference analog —
# the reference timeline is strictly per-rank). TRACE: a DIRECTORY; each
# worker writes DIR/trace.<rank>.json with per-hop child spans + clock
# metadata (hvdrun --trace collects and merges them at job end via
# scripts/trace_analyze.py). TRACE_SAMPLE: emit the per-hop span firehose
# for every Nth collective op (default 10 when tracing; 1 = every op,
# 0 = op-level phases only). TRACE_CLOCK_SYNC_SECONDS: how often a worker
# refreshes its clock offset vs rank 0 through the control plane while a
# trace is running (the form-up ping-pong sync always happens).
HVDTPU_TRACE = "HVDTPU_TRACE"
HVDTPU_TRACE_SAMPLE = "HVDTPU_TRACE_SAMPLE"
HVDTPU_TRACE_CLOCK_SYNC_SECONDS = "HVDTPU_TRACE_CLOCK_SYNC_SECONDS"

# Default every-Nth-op hop-span sampling rate while tracing.
DEFAULT_TRACE_SAMPLE = 10

# Always-on flight recorder (native/flightrec.{h,cpp} +
# horovod_tpu/flightrec.py; docs/fault-tolerance.md "Post-mortem
# debugging"). FLIGHTREC: "1" (default) keeps the in-memory ring of compact
# binary phase records live on every rank — unsampled, JSON-free, inside
# the <2% observability budget; "0" disables. FLIGHTREC_EVENTS: ring
# capacity in records (default 4096, ~160 KB). FLIGHTREC_DIR: directory
# for the automatic flightrec.<rank>.bin dumps on abort cascade / stall
# escalation / fatal signals (unset = in-memory only; the /debugz endpoint
# and hvdtpu_flightrec_snapshot still work). `hvdrun --postmortem DIR`
# sets it and runs scripts/postmortem.py on job failure.
HVDTPU_FLIGHTREC = "HVDTPU_FLIGHTREC"
HVDTPU_FLIGHTREC_EVENTS = "HVDTPU_FLIGHTREC_EVENTS"
HVDTPU_FLIGHTREC_DIR = "HVDTPU_FLIGHTREC_DIR"

# Default flight-recorder ring capacity in records, and the sanity ceiling
# (16M records = 640 MB of ring) init enforces so a typo'd value fails
# naming the knob instead of dying in a native allocation. The native side
# floors nonzero capacities at 64 records.
DEFAULT_FLIGHTREC_EVENTS = 4096
MAX_FLIGHTREC_EVENTS = 16 * 1024 * 1024

# Always-on perf attribution (native/perfstats.{h,cpp} +
# horovod_tpu/perfstats.py; docs/observability.md "Live perf
# attribution"). PERFSTATS: "1" (default) streams per-op EWMA + P² p50/p99
# baselines of wall time and the wait/wire/reduce/codec phase buckets,
# keyed by {tensor-set signature, algo, transport, hier, compression, op} —
# unsampled, allocation-free, inside the shared <2% observability budget;
# "0" disables. PERF_SLOWDOWN_PCT: the slowdown sentry flags a completed
# op this many percent over its key's rolling baseline (ANOMALY flight
# event + hvdtpu_perf_anomalies_total{phase=...}); 0 disables the sentry,
# baselines keep streaming. PERF_MIN_SAMPLES: per-key warmup before the
# sentry may fire. PERF_PROFILE_DIR: directory where each rank persists
# perf_profile.<rank>.json at shutdown for the cross-run regression sentry
# (`hvdrun --perf-profile DIR` sets it and merges at job end;
# scripts/perf_diff.py compares two profiles).
HVDTPU_PERFSTATS = "HVDTPU_PERFSTATS"
HVDTPU_PERF_SLOWDOWN_PCT = "HVDTPU_PERF_SLOWDOWN_PCT"
HVDTPU_PERF_MIN_SAMPLES = "HVDTPU_PERF_MIN_SAMPLES"
HVDTPU_PERF_PROFILE_DIR = "HVDTPU_PERF_PROFILE_DIR"

DEFAULT_PERF_SLOWDOWN_PCT = 50.0
DEFAULT_PERF_MIN_SAMPLES = 20

# Numerical-health observability (native/gradstats.{h,cpp} +
# horovod_tpu/gradstats.py; docs/numerics.md). GRADSTATS: "1" (default)
# streams per-tensor gradient moments (L2 norm, absmax, NaN/Inf counts,
# folded into the fusion copy-in), per-key quantization MSE/SNR +
# error-feedback residual norms (accumulated inside the compressed-wire
# kernels), and the cross-rank divergence probe — inside the shared <2%
# observability budget; "0" disables the whole subsystem. NANCHECK: what
# the first NaN/Inf gradient does — "off" (count nothing), "warn"
# (default: NONFINITE flight event + hvdtpu_nonfinite_grads_total + WARN,
# the op proceeds), "abort" (fail-fast: the op errors naming the tensor,
# the world breaks, and the forensics dump carries the NONFINITE record).
# GRADCHECK_SAMPLE: every Nth allreduce, each rank crc32c-fingerprints its
# post-reduce output and rank 0 majority-votes the world — any minority is
# silent data corruption or non-determinism (DIVERGENCE flight event +
# hvdtpu_divergence_total{suspect=...}). Default 64; 0 disables the probe;
# must be uniform across ranks (the launcher's env broadcast guarantees
# it). GRAD_PROFILE_DIR: directory where each rank persists
# grad_profile.<rank>.json at shutdown for the cross-run quality sentry
# (`hvdrun --grad-profile DIR` sets it and merges at job end;
# scripts/grad_diff.py compares two profiles).
HVDTPU_GRADSTATS = "HVDTPU_GRADSTATS"
HVDTPU_NANCHECK = "HVDTPU_NANCHECK"
HVDTPU_GRADCHECK_SAMPLE = "HVDTPU_GRADCHECK_SAMPLE"
HVDTPU_GRAD_PROFILE_DIR = "HVDTPU_GRAD_PROFILE_DIR"

DEFAULT_GRADCHECK_SAMPLE = 64

# In-process sampling profiler (native/profiler.{h,cpp} +
# horovod_tpu/profiler.py; docs/profiling.md). PROF: "1" (default) keeps
# the subsystem armed — per-thread SIGPROF timers exist but fire only
# while a sampling window runs (/profz, hvd.profile(), hvdrun --profile);
# "0" removes even that. PROF_HZ: sampling rate per thread (default 97 —
# prime, so the sampler cannot phase-lock with millisecond-periodic
# loops). PROF_CLOCK: "cpu" samples only while the thread burns cycles
# (the flamegraph contract); "wall" samples blocked time too, matching
# the perf-attribution wall buckets. PROF_DIR: directory where each rank
# writes prof.<rank>.folded at shutdown AND the switch that runs the
# window for the whole job (`hvdrun --profile DIR` sets it and merges at
# job end via scripts/prof_report.py).
HVDTPU_PROF = "HVDTPU_PROF"
HVDTPU_PROF_HZ = "HVDTPU_PROF_HZ"
HVDTPU_PROF_CLOCK = "HVDTPU_PROF_CLOCK"
HVDTPU_PROF_DIR = "HVDTPU_PROF_DIR"

DEFAULT_PROF_HZ = 97
MAX_PROF_HZ = 1000
# hvdtpu::ProfClock (native/profiler.h; scripts/check_invariants.py
# ENUM-MIRROR).
PROF_CLOCK_MODES = {"cpu": 0, "wall": 1}

# Autotune (reference: HOROVOD_AUTOTUNE, HOROVOD_AUTOTUNE_LOG,
# horovod/common/operations.cc:474-532)
HVDTPU_AUTOTUNE = "HVDTPU_AUTOTUNE"
HVDTPU_AUTOTUNE_LOG = "HVDTPU_AUTOTUNE_LOG"
HVDTPU_AUTOTUNE_WARMUP_SAMPLES = "HVDTPU_AUTOTUNE_WARMUP_SAMPLES"
HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE = "HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE"
HVDTPU_AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HVDTPU_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
HVDTPU_AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "HVDTPU_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"

# Live metrics (native/metrics.{h,cpp} + horovod_tpu/observability.py; no
# reference analog — the reference's only runtime visibility is the
# post-hoc timeline). METRICS_PORT is the BASE port: worker rank r serves
# /metrics + /healthz on base+r on its host; hvdrun's driver aggregator
# serves the merged world view on base+world_size and prints a periodic
# one-line summary. 0 (default) disables the endpoints (the in-process
# hvd.metrics() dict and hvdtpu_metrics_dump C API always work).
# METRICS_INTERVAL: driver scrape/summary period in seconds.
HVDTPU_METRICS_PORT = "HVDTPU_METRICS_PORT"
HVDTPU_METRICS_INTERVAL = "HVDTPU_METRICS_INTERVAL"

# Logging (reference: HOROVOD_LOG_LEVEL, HOROVOD_LOG_HIDE_TIME —
# horovod/common/logging.cc)
HVDTPU_LOG_LEVEL = "HVDTPU_LOG_LEVEL"
HVDTPU_LOG_HIDE_TIME = "HVDTPU_LOG_HIDE_TIME"

# Compression subsystem (reference fork knobs: horovod/common/common.h:96-108 —
# HOROVOD_COMPRESSION, HOROVOD_REDUCTION, HOROVOD_COMMUNICATOR,
# HOROVOD_QUANTIZATION_BITS, HOROVOD_COMPRESSION_BUCKET_SIZE,
# HOROVOD_COMPRESSION_ERROR_FEEDBACK, HOROVOD_COMPRESSION_TOPK_RATIO,
# HOROVOD_COMPRESSION_CONFIG_FILE)
HVDTPU_COMPRESSION = "HVDTPU_COMPRESSION"
HVDTPU_REDUCTION = "HVDTPU_REDUCTION"
HVDTPU_COMMUNICATOR = "HVDTPU_COMMUNICATOR"
HVDTPU_QUANTIZATION_BITS = "HVDTPU_QUANTIZATION_BITS"
HVDTPU_COMPRESSION_BUCKET_SIZE = "HVDTPU_COMPRESSION_BUCKET_SIZE"
HVDTPU_COMPRESSION_ERROR_FEEDBACK = "HVDTPU_COMPRESSION_ERROR_FEEDBACK"
HVDTPU_COMPRESSION_TOPK_RATIO = "HVDTPU_COMPRESSION_TOPK_RATIO"
HVDTPU_COMPRESSION_CONFIG_FILE = "HVDTPU_COMPRESSION_CONFIG_FILE"
# reference: HOROVOD_COMPRESSION_NORM_TYPE ("l2" | "linf") for the
# normalized quantizers (common.h:96-108).
HVDTPU_COMPRESSION_NORM_TYPE = "HVDTPU_COMPRESSION_NORM_TYPE"

# Wire-level compression in the native process-mode data plane
# (native/compressed.{h,cpp}; reference: the fork's ops/compressed/
# subsystem quantizing the MPI/SHM/P2P wire). HVDTPU_COMPRESSION doubles as
# the selector: the wire modes none|fp16|int8|int4|auto map directly
# ("auto" hands the choice to the Bayesian autotuner); "maxmin" rides its
# HVDTPU_QUANTIZATION_BITS (8 -> int8, 4 -> int4) so one knob drives the
# JAX and wire paths identically; the JAX-only compressors (bf16, uni, exp,
# topk) leave the wire dense. MIN_BYTES: allreduces below this payload stay
# raw (headers + extra passes would cost more than they save).
# SKIP_REGEX: case-insensitive regex over tensor names — matching ops stay
# dense (biases / norm layers, the fork's per-layer ignore rules).
HVDTPU_COMPRESSION_MIN_BYTES = "HVDTPU_COMPRESSION_MIN_BYTES"
HVDTPU_COMPRESSION_SKIP_REGEX = "HVDTPU_COMPRESSION_SKIP_REGEX"

# Wire modes, mapped to hvdtpu::WireCompression (native/compressed.h).
WIRE_COMPRESSION_MODES = {"none": 0, "fp16": 1, "int8": 2, "int4": 3,
                          "auto": 4}
# HVDTPU_COMPRESSION values that configure only the JAX-level compressors
# (compression/config.py) and keep the native wire dense.
JAX_ONLY_COMPRESSORS = ("bf16", "uni", "exp", "topk")
DEFAULT_COMPRESSION_MIN_BYTES = 1024
DEFAULT_COMPRESSION_SKIP_REGEX = r"bias|batch_?norm|layer_?norm"


def get_wire_compression(name: str, bits: int = 4) -> int:
    """Resolve an HVDTPU_COMPRESSION value to the native WireCompression
    code, validating the full accepted vocabulary (wire modes + JAX-level
    compressor names)."""
    name = (name or "none").strip().lower()
    if name in WIRE_COMPRESSION_MODES:
        return WIRE_COMPRESSION_MODES[name]
    if name == "maxmin":
        if bits == 8:
            return WIRE_COMPRESSION_MODES["int8"]
        if bits == 4:
            return WIRE_COMPRESSION_MODES["int4"]
        return WIRE_COMPRESSION_MODES["none"]  # 1/2-bit: JAX path only
    if name in JAX_ONLY_COMPRESSORS:
        return WIRE_COMPRESSION_MODES["none"]
    raise ValueError(
        f"{HVDTPU_COMPRESSION} must be one of "
        f"{sorted(WIRE_COMPRESSION_MODES)} + "
        f"{sorted(('maxmin',) + JAX_ONLY_COMPRESSORS)}, got {name!r}")

# Elastic (reference: HOROVOD_ELASTIC_TIMEOUT, HOROVOD_GLOO_TIMEOUT_SECONDS)
HVDTPU_ELASTIC_TIMEOUT = "HVDTPU_ELASTIC_TIMEOUT"

# Fault tolerance (docs/fault-tolerance.md; no reference analog — the
# reference's only escalation is the 60 s stall inspector).
# FAILURE_DETECT_MS bounds how long a peer death can go unnoticed on a
# blocked transport op (the data plane polls in detect_ms/5 slices, so an
# abort or EOF breaks every in-flight segmented send within one slice).
HVDTPU_FAILURE_DETECT_MS = "HVDTPU_FAILURE_DETECT_MS"
# Transport-level no-progress deadline in seconds: a lane that is open but
# moves ZERO bytes for this long mid-collective is declared dead — the only
# way to catch a hung-but-alive peer or a silently blackholed route (no EOF
# ever arrives). Progress resets the clock; 0 disables.
HVDTPU_READ_DEADLINE_SECONDS = "HVDTPU_READ_DEADLINE_SECONDS"
# Bounds rendezvous + data-plane mesh establishment: a rank that died
# between spawn and HELLO (or between rendezvous and its data-plane
# connect) fails form-up within this window instead of wedging it forever.
HVDTPU_FORMUP_TIMEOUT_SECONDS = "HVDTPU_FORMUP_TIMEOUT_SECONDS"
# Fault injection (horovod_tpu/chaos.py grammar -> hvdtpu_set_chaos): arm
# one one-shot kill/hang/delay/drop at an op or hop index, e.g.
# "rank1:kill@op=3". Forwarded to one random worker by `hvdrun --chaos`.
HVDTPU_CHAOS = "HVDTPU_CHAOS"

# Mesh / SPMD-mode knobs (TPU-native, no reference analog: control how the
# single-process device mesh is laid out).
HVDTPU_MESH_SHAPE = "HVDTPU_MESH_SHAPE"
HVDTPU_DP_AXIS = "HVDTPU_DP_AXIS"

# Native-library override: point the ctypes loader at an alternative build of
# libhvdtpu_core.so — the sanitizer suites (native/Makefile tsan/asan/ubsan
# targets) rerun the process-mode tests against instrumented builds this way.
HVDTPU_NATIVE_LIB = "HVDTPU_NATIVE_LIB"

# PowerSGD error-feedback residual accounting (compression/powersgd.py):
# CAP = hard ceiling in BYTES on total residual state (init raises above
# it), WARN = byte threshold that logs a warning (default 1 GiB).
HVDTPU_POWERSGD_RESIDUAL_CAP = "HVDTPU_POWERSGD_RESIDUAL_CAP"
HVDTPU_POWERSGD_RESIDUAL_WARN = "HVDTPU_POWERSGD_RESIDUAL_WARN"

# ---------------------------------------------------------------------------
# Internal variables: set by the launcher / test harness for its own child
# processes, never meant to be set by users (docs/envvars.md "Internal").
# Declared here so the invariant linter (scripts/check_invariants.py) can
# verify every HVDTPU_* string in the tree against this registry.
# ---------------------------------------------------------------------------

# Elastic worker identity token, injected per-attempt by the elastic driver
# (runner/elastic/driver.py) and echoed in state-sync commits.
HVDTPU_WORKER_ID = "HVDTPU_WORKER_ID"
# One-shot marker file for HVDTPU_CHAOS under elastic restarts: the first
# process to arm the spec creates it, so a respawned worker inheriting the
# dead worker's rank does not re-arm the same fault (horovod_tpu/chaos.py).
HVDTPU_CHAOS_MARKER = "HVDTPU_CHAOS_MARKER"
# runner.run()'s function-shipping KV store address, injected into workers.
HVDTPU_RUN_KV_ADDR = "HVDTPU_RUN_KV_ADDR"
HVDTPU_RUN_KV_PORT = "HVDTPU_RUN_KV_PORT"
# Connectivity-preflight probe parameters (runner/preflight.py _probe_main:
# the probe subprocess reads its marching orders from these).
HVDTPU_PREFLIGHT_KV_ADDR = "HVDTPU_PREFLIGHT_KV_ADDR"
HVDTPU_PREFLIGHT_KV_PORT = "HVDTPU_PREFLIGHT_KV_PORT"
HVDTPU_PREFLIGHT_HOST = "HVDTPU_PREFLIGHT_HOST"
HVDTPU_PREFLIGHT_ROLE = "HVDTPU_PREFLIGHT_ROLE"
HVDTPU_PREFLIGHT_CONTROLLER = "HVDTPU_PREFLIGHT_CONTROLLER"
HVDTPU_PREFLIGHT_TIMEOUT = "HVDTPU_PREFLIGHT_TIMEOUT"

# Names the invariant linter requires to be documented under
# docs/envvars.md's "## Internal" section rather than a user-facing table
# (ENV-DOC in scripts/check_invariants.py).
INTERNAL_ENV_VARS = frozenset({
    HVDTPU_WORKER_ID,
    HVDTPU_CHAOS_MARKER,
    HVDTPU_RUN_KV_ADDR,
    HVDTPU_RUN_KV_PORT,
    HVDTPU_PREFLIGHT_KV_ADDR,
    HVDTPU_PREFLIGHT_KV_PORT,
    HVDTPU_PREFLIGHT_HOST,
    HVDTPU_PREFLIGHT_ROLE,
    HVDTPU_PREFLIGHT_CONTROLLER,
    HVDTPU_PREFLIGHT_TIMEOUT,
})


def get_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}")


def get_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name} must be a float, got {v!r}")


def get_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def get_str(name: str, default: Optional[str] = None) -> Optional[str]:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v


def get_required(name: str) -> str:
    """A variable the caller cannot proceed without (launcher-injected
    internals like the preflight probe parameters). Raises KeyError like a
    raw ``os.environ[name]`` would, so existing failure modes are
    unchanged."""
    return os.environ[name]
