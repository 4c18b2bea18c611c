"""Topology runtime: ``init`` / ``rank`` / ``size`` / device mesh.

Reference surface: ``horovod/common/basics.py:22`` (``HorovodBasics`` — ``init``,
``shutdown``, ``rank``, ``size``, ``local_rank``, ``local_size``, ``cross_rank``,
``cross_size``, ``is_initialized``, ``is_homogeneous``) backed by the C API in
``horovod/common/operations.cc:705-913``.

TPU-native redesign
-------------------
The reference assumes one process per accelerator, ranks negotiated by MPI/Gloo.
On TPU the native regime is SPMD: one process per *host*, all chips driven through a
``jax.sharding.Mesh``, collectives compiled by XLA onto ICI. We therefore support two
modes, selected automatically:

* **spmd** (default): ``init()`` builds a mesh over all global devices (multi-host via
  ``jax.distributed``). A *rank* is a device; ``size()`` is the global device count;
  ``rank()`` at host level is this process's first device index (so ``rank() == 0``
  checkpoint guards behave like Horovod's). Inside a step wrapped by
  :func:`horovod_tpu.run_step` (shard_map over the mesh), ``rank_in_step()`` gives the
  per-device rank.
* **process**: Horovod-parity one-rank-per-process mode, selected when the
  ``hvdrun`` launcher exported ``HVDTPU_RANK``/``HVDTPU_SIZE`` (reference env
  injection: ``horovod/runner/gloo_run.py:70-95``). Eager named-tensor collectives run
  through the native C++ controller (``horovod_tpu/native``), no MPI/NCCL.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from .exceptions import NotInitializedError
from .utils import envvars as ev
from .utils import logging as log

# The default mesh axis name for data parallelism. Additional axes ("tp", "sp",
# "pp", "ep") are created on demand via init(mesh_shape=...).
DP_AXIS = "dp"


@dataclasses.dataclass
class _RuntimeState:
    initialized: bool = False
    mode: str = "spmd"  # "spmd" | "process"
    # Horovod-style topology (process mode: per-process; spmd: derived from devices).
    rank: int = 0
    size: int = 1
    local_rank: int = 0
    local_size: int = 1
    cross_rank: int = 0
    cross_size: int = 1
    homogeneous: bool = True
    # SPMD state.
    mesh: Optional[object] = None  # jax.sharding.Mesh
    axis_names: Tuple[str, ...] = (DP_AXIS,)
    dp_axis: str = DP_AXIS
    # Process-mode native controller handle (horovod_tpu.basics.NativeCore).
    core: Optional[object] = None
    # Per-worker /metrics + /healthz endpoint (horovod_tpu.observability),
    # started when HVDTPU_METRICS_PORT > 0 in process mode.
    metrics_server: Optional[object] = None
    # Monotonic epoch, bumped on shutdown/re-init (elastic resets).
    epoch: int = 0
    # SPMD mode: compile, start-up and placement counters behind
    # hvd.metrics(), host spans for hvd.start_timeline
    # (horovod_tpu.spmd_recorder.SpmdRecorder).
    recorder: Optional[object] = None


_state = _RuntimeState()
_lock = threading.RLock()


class _SingleRankCore:
    """Pure-Python stand-in for the native core at world size 1 when the
    compiled library is unavailable: collectives degenerate to local math
    (allreduce/broadcast/allgather/alltoall/reducescatter of one rank are
    the input, modulo pre/postscale). No timeline, autotune, or stall
    inspection — a degraded but working mode for source-only installs."""

    def __init__(self):
        self._results = {}
        self._next = 0

    def start(self):
        pass

    def shutdown(self):
        pass

    def enqueue(self, kind, name, arr, op=1, prescale=1.0, postscale=1.0,
                root_rank=0, splits=None):
        out = np.asarray(arr)
        if kind in ("allreduce", "reducescatter") and \
                (prescale != 1.0 or postscale != 1.0):
            out = out * (prescale * postscale)
        h = self._next
        self._next += 1
        self._results[h] = out
        return h

    def poll(self, handle):
        return True

    def wait(self, handle, out_dtype, row_shape):
        return self._results.pop(handle)

    def collective(self, kind, name, arr, **kw):
        return self.wait(self.enqueue(kind, name, arr, **kw), None, None)

    def join(self):
        return 0

    def start_timeline(self, path, mark_cycles=False):
        log.warning("timeline requires the compiled native core; ignoring")

    def stop_timeline(self):
        pass

    def cycle_time_ms(self):
        return 0.0

    def fusion_threshold(self):
        return 0

    def metrics_dump(self):
        return ""  # no native registry without the compiled core

    def metrics(self):
        return {}


_init_kwargs: dict = {}


def _detect_mode() -> str:
    if ev.get_str(ev.HVDTPU_SIZE) or ev.get_str(ev.HVDTPU_RENDEZVOUS_ADDR):
        return "process"
    return "spmd"


# Last rendezvous epoch this process initialized with (elastic mode): re-init
# only accepts a NEWER epoch, which removes the failed-peer/stale-epoch race.
_elastic_last_epoch = 0

# When the elastic retry loop detected a peer failure (monotonic seconds):
# consumed by the next successful process-mode init, which records the
# detection-to-reformation latency against the NEW core's registry
# (hvdtpu_recovery_seconds; docs/fault-tolerance.md).
_failure_detected_at: Optional[float] = None


def note_failure_detected() -> None:
    """Mark the moment a peer failure was detected (called by the elastic
    retry loop on HvdTpuInternalError). The FIRST detection of an episode
    wins — repeated failures before a successful re-init are one outage."""
    global _failure_detected_at
    if _failure_detected_at is None:
        _failure_detected_at = time.monotonic()


def _elastic_assignment() -> Optional[dict]:
    """Poll the elastic driver's KV store for this worker's assignment
    (keys documented in horovod_tpu/runner/elastic/driver.py; fills the role
    of the reference's rendezvous GET, elastic/rendezvous.py)."""
    global _elastic_last_epoch
    addr = ev.get_str(ev.HVDTPU_RENDEZVOUS_ADDR)
    if not addr:
        return None
    import json
    import sys
    import time as _time

    from .runner.http_kv import KVStoreClient
    port = ev.get_int(ev.HVDTPU_RENDEZVOUS_PORT, 0)
    worker_id = ev.get_str(ev.HVDTPU_WORKER_ID)
    client = KVStoreClient(addr, port,
                           secret=ev.get_str(ev.HVDTPU_SECRET) or None)
    timeout = ev.get_float(ev.HVDTPU_ELASTIC_TIMEOUT, 600.0)
    deadline = _time.monotonic() + timeout
    missing_since = None
    while _time.monotonic() < deadline:
        try:
            raw = client.get("/rendezvous/epoch")
        except Exception:
            # Transient KV hiccup (driver mid-restart / connection reset):
            # retry until the elastic timeout rather than dying — a non-zero
            # exit would get this worker's healthy host blacklisted.
            raw = None
        if raw:
            epoch = int(raw)
            if epoch > _elastic_last_epoch:
                try:
                    a = client.get(
                        f"/rendezvous/{epoch}/assignment/{worker_id}")
                except Exception:
                    a = None
                if a:
                    _elastic_last_epoch = epoch
                    try:
                        # Claim the assignment: the driver's settle watchdog
                        # terminates+respawns workers that never post this
                        # (a rank wedged inside the PREVIOUS world cannot
                        # re-enter rendezvous — without the claim it would
                        # hold its slot and livelock every new epoch).
                        client.put(f"/rendezvous/{epoch}/ready/{worker_id}",
                                   b"1")
                    except Exception:
                        pass  # claim is advisory; the watchdog respawns us
                    return json.loads(a)
                # Epoch advanced without us: scaled away. Give the driver a
                # short grace window in case a newer epoch re-adds us.
                if missing_since is None:
                    missing_since = _time.monotonic()
                elif _time.monotonic() - missing_since > 5.0:
                    log.info("elastic: worker %s removed from epoch %d; "
                             "exiting cleanly", worker_id, epoch)
                    sys.exit(0)
        _time.sleep(0.25)
    raise TimeoutError("elastic rendezvous timed out")


_jax_distributed_done = False


def _maybe_init_jax_distributed() -> None:
    """Multi-host SPMD bootstrap: call ``jax.distributed.initialize`` so
    every host sees the GLOBAL device set before the mesh is built
    (the control-plane role MPI_Init / gloo rendezvous plays in the
    reference, SURVEY §2.7 — on TPU pods the coordinator rides DCN).

    Opt-in: explicit coordinator via ``HVDTPU_COORDINATOR_ADDR`` (+
    ``HVDTPU_NUM_PROCESSES`` / ``HVDTPU_PROCESS_ID``), or
    ``HVDTPU_AUTO_DISTRIBUTED=1`` for Cloud-TPU metadata auto-detection.
    Single-host runs (the default) skip it entirely — calling initialize
    on a lone CPU host would hang waiting for a coordinator.
    """
    global _jax_distributed_done
    if _jax_distributed_done:
        return
    import jax

    coord = ev.get_str(ev.HVDTPU_COORDINATOR_ADDR)
    auto = ev.get_bool(ev.HVDTPU_AUTO_DISTRIBUTED)
    if not coord and not auto:
        return
    kwargs = {}
    if coord:
        # Explicit coordinator: the full triple is REQUIRED. A missing
        # HVDTPU_PROCESS_ID would silently default every host to process 0
        # and the job would hang deep inside the coordinator with no hint
        # which env var is missing.
        nproc = ev.get_int(ev.HVDTPU_NUM_PROCESSES, 0)
        pid = ev.get_str(ev.HVDTPU_PROCESS_ID)
        if not nproc or pid is None or pid == "":
            raise ValueError(
                "HVDTPU_COORDINATOR_ADDR requires HVDTPU_NUM_PROCESSES and "
                "HVDTPU_PROCESS_ID to be set explicitly on every host "
                "(or use HVDTPU_AUTO_DISTRIBUTED=1 on managed clusters)")
        kwargs["coordinator_address"] = coord
        kwargs["num_processes"] = nproc
        kwargs["process_id"] = int(pid)
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        if "already" not in str(e).lower():
            raise
    _jax_distributed_done = True
    log.info("init: jax.distributed ready (process %d/%d, %d global devices)",
             jax.process_index(), jax.process_count(), len(jax.devices()))


# Where the persistent XLA compilation cache lives when nobody places it from
# outside: one fixed path inside the checkout. The path is part of the cache
# key, so it must not move between runs — no temp dir, pid or timestamp.
_DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def _place_compilation_cache() -> None:
    """Persistent XLA compilation cache: restarts — elastic resets, respawned
    jobs, the next run of the same command — reuse prior compiles instead of
    paying the first-compile again. ``JAX_COMPILATION_CACHE_DIR`` places it
    from outside and JAX reads that variable itself, so then nothing is set
    here; otherwise it sits at ``<checkout>/.jax_cache``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      _DEFAULT_COMPILATION_CACHE_DIR)


def _build_mesh(mesh_shape, axis_names, devices):
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if mesh_shape is None:
        shape_env = ev.get_str(ev.HVDTPU_MESH_SHAPE)
        if shape_env:
            # e.g. "dp=4,tp=2"
            mesh_shape = {}
            for part in shape_env.split(","):
                k, v = part.split("=")
                mesh_shape[k.strip()] = int(v)
        else:
            mesh_shape = {DP_AXIS: n}
    if isinstance(mesh_shape, dict):
        axis_names = tuple(mesh_shape.keys())
        dims = tuple(mesh_shape.values())
    else:
        dims = tuple(mesh_shape)
        axis_names = tuple(axis_names)
    total = int(np.prod(dims)) if dims else 1
    if total != n:
        raise ValueError(
            f"mesh_shape {dims} (={total} devices) does not match the "
            f"{n} available devices")
    dev_array = np.asarray(devices).reshape(dims)
    return Mesh(dev_array, axis_names), axis_names


def init(comm: Optional[Sequence[int]] = None,
         mode: Optional[str] = None,
         mesh_shape=None,
         axis_names: Sequence[str] = (DP_AXIS,),
         dp_axis: str = DP_AXIS,
         devices=None) -> None:
    """Initialize the runtime.

    Mirrors ``hvd.init()`` (reference ``horovod/common/basics.py:34``; ``comm`` as a
    rank subset is accepted for signature parity but only the full world is
    supported). Safe to call twice (second call is a no-op, like the reference's
    ``InitializeHorovodOnce``, ``operations.cc:648``).

    Args:
      mode: "spmd", "process", or None to auto-detect (process mode iff the
        launcher exported ``HVDTPU_SIZE``).
      mesh_shape: SPMD mode — dict ``{"dp": 4, "tp": 2}`` or tuple of dims for the
        device mesh; default is a 1-D data-parallel mesh over all devices.
      axis_names: names for tuple-form ``mesh_shape``.
      dp_axis: which mesh axis is the data-parallel (Horovod-rank) axis.
      devices: explicit device list (testing); default ``jax.devices()``.
    """
    global _state, _init_kwargs
    with _lock:
        if _state.initialized:
            return
        # Remember the call signature so elastic resets re-initialize with the
        # same topology (mesh shape, axis names, mode).
        _init_kwargs = dict(comm=comm, mode=mode, mesh_shape=mesh_shape,
                            axis_names=axis_names, dp_axis=dp_axis,
                            devices=devices)
        cache_t0 = time.time_ns()
        _place_compilation_cache()
        cache_t1 = time.time_ns()
        mode = mode or _detect_mode()
        st = _RuntimeState(mode=mode, epoch=_state.epoch + 1)
        if mode == "process":
            assignment = _elastic_assignment()
            controller = (None, None)
            if assignment is not None:
                st.rank = assignment["rank"]
                st.size = assignment["size"]
                st.local_rank = assignment["local_rank"]
                st.local_size = assignment["local_size"]
                st.cross_rank = assignment["cross_rank"]
                st.cross_size = assignment["cross_size"]
                controller = (assignment["controller_addr"],
                              assignment["controller_port"])
            else:
                st.rank = ev.get_int(ev.HVDTPU_RANK, 0)
                st.size = ev.get_int(ev.HVDTPU_SIZE, 1)
                st.local_rank = ev.get_int(ev.HVDTPU_LOCAL_RANK, 0)
                st.local_size = ev.get_int(ev.HVDTPU_LOCAL_SIZE, 1)
                st.cross_rank = ev.get_int(ev.HVDTPU_CROSS_RANK, st.rank)
                st.cross_size = ev.get_int(ev.HVDTPU_CROSS_SIZE, st.size)
            # The native core runs at every world size — a single-rank job
            # still gets the background loop, timeline, and identical op
            # semantics (the reference behaves the same at np=1). Pure-Python
            # installs (no compiled .so) keep working at size 1 only, with
            # collectives degenerating to local math.
            try:
                from . import basics
                st.core = basics.NativeCore(
                    rank=st.rank, size=st.size,
                    local_rank=st.local_rank, local_size=st.local_size,
                    cross_rank=st.cross_rank, cross_size=st.cross_size,
                    coord_host=controller[0], coord_port=controller[1])
            except (ImportError, OSError,
                    subprocess.CalledProcessError) as e:
                if st.size == 1:
                    log.warning(
                        "native core unavailable (%s); single-rank process "
                        "mode continues without it (no timeline/autotune). "
                        "Build with `make -C horovod_tpu/native` for the "
                        "full runtime.", e)
                    st.core = _SingleRankCore()
                    st.initialized = True
                    _state = st
                    return
                raise NotInitializedError(
                    "process mode requires the native core binding "
                    "(horovod_tpu/basics.py + horovod_tpu/native); build "
                    "it with `make -C horovod_tpu/native`") from e
            try:
                st.core.start()
            except Exception:
                # A failed form-up (peer died mid-rendezvous) must release
                # the half-joined core — its listen socket and controller
                # connection would otherwise leak into the retry.
                st.core.shutdown()
                raise
            # Elastic recovery accounting: the world re-formed after a
            # detected failure — record detection -> re-init latency in the
            # new core so hvd.metrics() shows the episode.
            global _failure_detected_at
            if _failure_detected_at is not None:
                if hasattr(st.core, "observe_recovery"):
                    st.core.observe_recovery(
                        time.monotonic() - _failure_detected_at)
                _failure_detected_at = None
            # Per-worker live-metrics endpoint: rank r serves /metrics +
            # /healthz on HVDTPU_METRICS_PORT + r (0 = off), secret-gated
            # like the rendezvous KV server. Started after the core so a
            # scrape never races init; a bind failure is fatal and names
            # the knob (hvdrun preflights the ports before spawning).
            metrics_base = ev.get_int(ev.HVDTPU_METRICS_PORT, 0)
            if metrics_base > 0:
                from .observability import MetricsServer
                port = metrics_base + st.rank

                def _debugz(core=st.core):
                    # Flight-recorder live view next to /metrics: in-flight
                    # op + last-N ring events (docs/fault-tolerance.md).
                    from .flightrec import debugz_json
                    snap = (core.flightrec_snapshot()
                            if hasattr(core, "flightrec_snapshot") else b"")
                    return debugz_json(snap)

                def _perfz(core=st.core):
                    # Live perf attribution next to /metrics: the streaming
                    # per-key baselines + anomaly counts as JSON
                    # (docs/observability.md).
                    snap = (core.perfstats_snapshot()
                            if hasattr(core, "perfstats_snapshot") else b"")
                    return snap.decode() if snap else \
                        '{"version": 1, "enabled": false, "keys": []}'

                def _gradz(core=st.core):
                    # Numerical health next to /metrics: per-tensor
                    # gradient norms, per-key quantization SNR, and the
                    # NaN/divergence totals as JSON (docs/numerics.md).
                    snap = (core.gradstats_snapshot()
                            if hasattr(core, "gradstats_snapshot") else b"")
                    return snap.decode() if snap else \
                        '{"version": 1, "enabled": false, "keys": []}'

                def _profz(query, core=st.core):
                    # Sampling profiler next to /metrics (docs/profiling.md):
                    # ?start / ?stop drive the window, a plain GET returns
                    # the folded-stacks JSON.
                    if not hasattr(core, "profiler_snapshot"):
                        return ('{"version": 1, "enabled": false, '
                                '"stacks": []}')
                    if query == "start":
                        core.profiler_start()
                        return '{"profiler": "started"}'
                    if query == "stop":
                        core.profiler_stop()
                        return '{"profiler": "stopped"}'
                    snap = core.profiler_snapshot()
                    return snap.decode() if snap else \
                        '{"version": 1, "enabled": false, "stacks": []}'

                try:
                    st.metrics_server = MetricsServer(
                        dump_fn=st.core.metrics_dump, port=port,
                        secret=ev.get_str(ev.HVDTPU_SECRET) or None,
                        health={"rank": st.rank, "size": st.size},
                        debugz_fn=_debugz, perfz_fn=_perfz,
                        profz_fn=_profz, gradz_fn=_gradz)
                except OSError as exc:
                    # The core already joined the world — tear it down
                    # before failing or it would linger as a zombie rank
                    # (holding the controller connection, and on rank 0
                    # the controller port) past this failed init.
                    st.core.shutdown()
                    raise NotInitializedError(
                        f"cannot bind the metrics endpoint on port {port} "
                        f"({ev.HVDTPU_METRICS_PORT}={metrics_base} + rank "
                        f"{st.rank}): {exc}") from exc
                st.metrics_server.start()
            log.debug("init: process mode rank=%d size=%d local=%d/%d",
                      st.rank, st.size, st.local_rank, st.local_size)
        else:
            import jax
            from .spmd_recorder import SpmdRecorder
            st.recorder = SpmdRecorder()
            st.recorder.phase("compile_cache", cache_t0, cache_t1)
            # The first jax.devices() of a process starts the backend: on a
            # TPU host that is the chips' start-up, seconds that vary from
            # run to run and are not the program's.
            t0 = time.time_ns()
            _maybe_init_jax_distributed()
            jax.devices()
            t1 = time.time_ns()
            st.recorder.phase("backend", t0, t1)
            st.mesh, st.axis_names = _build_mesh(mesh_shape, axis_names, devices)
            st.dp_axis = dp_axis if dp_axis in st.axis_names else st.axis_names[0]
            st.size = int(np.prod(list(st.mesh.shape.values())))
            local_idx = [i for i, d in enumerate(st.mesh.devices.flat)
                         if d.process_index == jax.process_index()]
            st.local_size = max(len(local_idx), 1)
            st.local_rank = 0
            # rank() == the first LOCAL device's global mesh index (not
            # process_index * local_size, which collides across hosts with
            # unequal device counts). A host contributing NO devices to the
            # mesh still needs a unique rank (rank-0 gates must not fire on
            # every such host): give it a slot past the device ranks.
            st.rank = local_idx[0] if local_idx else \
                st.size + jax.process_index()
            st.cross_rank = jax.process_index()
            st.cross_size = jax.process_count()
            first = st.mesh.devices.flat[0]
            st.recorder.phase("mesh", t1, time.time_ns())
            st.recorder.start()
            log.info("init: spmd mode platform=%s device_kind=%s devices=%d "
                     "mesh=%s", first.platform, first.device_kind, st.size,
                     dict(st.mesh.shape))
        st.initialized = True
        _state = st


def shutdown() -> None:
    """Tear down the runtime (reference: ``horovod_shutdown``, operations.cc:718)."""
    global _state
    with _lock:
        if not _state.initialized:
            return
        if _state.metrics_server is not None:
            _state.metrics_server.stop()
        if _state.core is not None:
            _state.core.shutdown()
        if _state.recorder is not None:
            _state.recorder.stop()
        _state = _RuntimeState(epoch=_state.epoch)
        # Compiled eager-collective programs close over the old Mesh; drop them
        # so elastic re-inits don't accumulate stale executables.
        from .ops import collectives as _C
        _C._sharded_collective_fn.cache_clear()
        _C._grouped_allreduce_fn.cache_clear()


def reinit() -> None:
    """Shutdown + init with the arguments from the last ``init`` call —
    used by elastic resets so the topology/mesh layout is preserved."""
    kwargs = dict(_init_kwargs)
    shutdown()
    init(**kwargs)


def is_initialized() -> bool:
    """Reference: ``horovod_is_initialized`` (operations.cc added 0.20)."""
    return _state.initialized


def _require_init() -> _RuntimeState:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def rank() -> int:
    """Global rank of this process (device rank of first local device in SPMD)."""
    return _require_init().rank


def size() -> int:
    """Number of ranks (SPMD: global device count)."""
    return _require_init().size


def local_rank() -> int:
    return _require_init().local_rank


def local_size() -> int:
    return _require_init().local_size


def cross_rank() -> int:
    return _require_init().cross_rank


def cross_size() -> int:
    return _require_init().cross_size


def is_homogeneous() -> bool:
    """True when every node has the same number of ranks
    (reference: ``horovod_is_homogeneous``, controller.cc)."""
    return _require_init().homogeneous


def mode() -> str:
    return _require_init().mode


def mesh():
    """The global :class:`jax.sharding.Mesh` (SPMD mode).

    Process mode builds a trivial 1-device mesh over this process's first device so
    compiled-path helpers still work.
    """
    st = _require_init()
    if st.mesh is None:
        st.mesh, st.axis_names = _build_mesh(None, (DP_AXIS,), None)
        st.dp_axis = st.axis_names[0]
    return st.mesh


def dp_axis() -> str:
    """Name of the data-parallel mesh axis."""
    return _require_init().dp_axis


def axis_names() -> Tuple[str, ...]:
    return _require_init().axis_names


def core():
    """Native controller handle (process mode, size > 1) or None."""
    return _require_init().core


def epoch() -> int:
    return _state.epoch


def recorder():
    """The SPMD recorder (:mod:`horovod_tpu.spmd_recorder`), or None before
    ``init`` and in process mode."""
    return _state.recorder


def note_traced(family: str, amount: int = 1, **labels) -> None:
    """One more trace (or ``amount`` more bytes or tiles) of a trace-time
    family of ``spmd_recorder._TRACED``, from code that JAX is tracing;
    nothing where there is no recorder (before ``init``, process mode)."""
    if _state.recorder is not None:
        _state.recorder.note_traced(family, amount, **labels)


def metrics() -> dict:
    """Live-metrics snapshot:
    ``{family: {"type", "help", "samples": [(suffix, labels, value)]}}``
    (the shape :func:`horovod_tpu.observability.parse_prometheus_text`
    gives; ``docs/metrics.md`` has the catalog). Process mode: the native
    core's registry. SPMD mode: the ``hvdtpu_spmd_*`` families, what JAX
    compiled for how long, the persistent cache's hits and misses, the
    phases of ``hvd.init()`` and what ``hvd.shard_batch`` placed."""
    st = _require_init()
    if st.recorder is not None:
        return st.recorder.families()
    from .observability import parse_prometheus_text
    text = metrics_dump()
    return parse_prometheus_text(text) if text else {}


def metrics_dump() -> str:
    """Prometheus text exposition of :func:`metrics`: in process mode the
    text the per-worker ``/metrics`` endpoint serves."""
    st = _require_init()
    if st.recorder is not None:
        from .observability import render_exposition
        return render_exposition(st.recorder.families())
    if st.core is not None and hasattr(st.core, "metrics_dump"):
        return st.core.metrics_dump()
    return ""


def metrics_server():
    """The worker's running :class:`horovod_tpu.observability.MetricsServer`
    (``HVDTPU_METRICS_PORT`` > 0 in process mode) or None."""
    return _require_init().metrics_server


def debugz(last_n: int = 50) -> dict:
    """Flight-recorder live view (docs/fault-tolerance.md "Post-mortem
    debugging"): this rank's in-flight op, last wire hop, and the last
    ``last_n`` ring events — the same JSON the worker's ``/debugz``
    endpoint serves. ``{"flightrec": "disabled"}`` when the recorder is
    off or outside process mode."""
    from .flightrec import debugz_dict
    st = _require_init()
    if st.core is None or not hasattr(st.core, "flightrec_snapshot"):
        return {"flightrec": "disabled"}
    return debugz_dict(st.core.flightrec_snapshot(), last_n=last_n)


def perf_report(parsed: bool = True):
    """Live perf-attribution snapshot (docs/observability.md "Live perf
    attribution"): this rank's streaming per-key baselines — EWMA + p50/p99
    of op wall time and the wait/wire/reduce/codec phase buckets — plus
    anomaly counts, the same JSON the worker's ``/perfz`` endpoint serves.
    ``parsed=False`` returns the human-readable table instead
    (:func:`horovod_tpu.perfstats.format_report`). ``{"perfstats":
    "disabled"}`` outside process mode or without the native core."""
    from .perfstats import format_report, parse_snapshot
    st = _require_init()
    if st.core is None or not hasattr(st.core, "perfstats_snapshot"):
        return {"perfstats": "disabled"}
    snap = st.core.perfstats_snapshot()
    if not snap:
        return {"perfstats": "disabled"}
    doc = parse_snapshot(snap)
    return doc if parsed else format_report(doc)


def grad_report(parsed: bool = True):
    """Numerical-health snapshot (docs/numerics.md): this rank's per-tensor
    gradient norms / absmax / NaN-Inf counts, per-key quantization MSE/SNR
    and error-feedback residual norms, plus the divergence-probe totals —
    the same JSON the worker's ``/gradz`` endpoint serves. ``parsed=False``
    returns the human-readable table instead
    (:func:`horovod_tpu.gradstats.format_report`). ``{"gradstats":
    "disabled"}`` outside process mode or without the native core."""
    from .gradstats import format_report, parse_snapshot
    st = _require_init()
    if st.core is None or not hasattr(st.core, "gradstats_snapshot"):
        return {"gradstats": "disabled"}
    snap = st.core.gradstats_snapshot()
    if not snap:
        return {"gradstats": "disabled"}
    doc = parse_snapshot(snap)
    return doc if parsed else format_report(doc)


def flightrec_dump(path: Optional[str] = None) -> bool:
    """On-demand flight-recorder dump to ``path`` (None = the configured
    ``HVDTPU_FLIGHTREC_DIR/flightrec.<rank>.bin``); decode with
    ``scripts/postmortem.py`` or :mod:`horovod_tpu.flightrec`. False when
    the recorder is disabled, no destination is known, or outside process
    mode."""
    st = _require_init()
    if st.core is None or not hasattr(st.core, "flightrec_dump"):
        return False
    return st.core.flightrec_dump(path)


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start writing the timeline (Chrome-trace JSON at ``file_path``) at
    runtime.

    Reference: ``hvd.start_timeline`` → ``horovod_start_timeline``
    (operations.cc:735-777). Process mode records negotiation/queue/op phases
    from the native background loop. In SPMD mode the collectives are
    compiled into the XLA program, so the device's side is a profiler trace
    of the device planes under ``file_path`` + ``.xplane`` (every instruction
    carries the program's scopes and kernel names), and ``file_path`` gets
    the host's side when the timeline stops: ``hvd.init`` phases, compiles,
    ``hvd.shard_batch`` calls and when each batch was ready, on the trace's
    own clock (docs/timeline.md).
    """
    st = _require_init()
    if st.core is not None:
        st.core.start_timeline(file_path, mark_cycles)
    elif st.recorder is not None:
        st.recorder.start_timeline(file_path)


def stop_timeline() -> None:
    """Stop a timeline started by :func:`start_timeline` (reference:
    ``horovod_stop_timeline``, operations.cc:780-790)."""
    st = _require_init()
    if st.core is not None:
        st.core.stop_timeline()
    elif st.recorder is not None:
        st.recorder.stop_timeline()


def start_trace(file_path: str, sample: Optional[int] = None,
                mark_cycles: bool = False) -> None:
    """Begin a distributed trace at runtime (docs/tracing.md).

    Process mode: a Chrome-trace timeline whose per-hop child spans
    (SEND/RECV/SENDRECV/REDUCE/QUANTIZE, with wait-vs-wire split) are
    sampled every ``sample`` collective ops (None keeps the configured
    ``HVDTPU_TRACE_SAMPLE`` rate, default 10) and whose metadata carries
    this rank's clock offset ± error vs rank 0 — merge the per-rank files
    with ``scripts/trace_analyze.py`` into one globally-aligned Perfetto
    trace plus a critical-path/straggler report. No extra tracing exists in
    SPMD mode (collectives are compiled into the XLA program); this falls
    back to :func:`start_timeline`'s XLA profiler trace there.
    """
    st = _require_init()
    if st.core is not None and hasattr(st.core, "start_trace"):
        st.core.start_trace(file_path, sample=sample,
                            mark_cycles=mark_cycles)
    else:
        start_timeline(file_path, mark_cycles=mark_cycles)


def stop_trace() -> None:
    """Stop a distributed trace started by :func:`start_trace`."""
    st = _require_init()
    if st.core is not None and hasattr(st.core, "stop_trace"):
        st.core.stop_trace()
    else:
        stop_timeline()


def prof_start() -> None:
    """Open a sampling-profiler window (docs/profiling.md): the native
    core's SIGPROF timers start firing at ``HVDTPU_PROF_HZ`` and every
    sample is tagged with the current collective phase and op. No-op
    outside process mode or with ``HVDTPU_PROF=0``."""
    st = _require_init()
    if st.core is not None and hasattr(st.core, "profiler_start"):
        st.core.profiler_start()


def prof_stop() -> None:
    """Close the sampling window; the ring keeps the window's samples for
    :func:`prof_snapshot`."""
    st = _require_init()
    if st.core is not None and hasattr(st.core, "profiler_stop"):
        st.core.profiler_stop()


def prof_snapshot(parsed: bool = True):
    """Folded-stacks snapshot of the current/last sampling window — the
    same JSON the worker's ``/profz`` endpoint serves: aggregated
    ``{phase, op, frames} -> count``, symbolized at snapshot time.
    ``parsed=False`` returns flamegraph.pl-compatible folded lines instead
    (:func:`horovod_tpu.profiler.to_folded_text`). ``{"profiler":
    "disabled"}`` outside process mode or without the native core."""
    from .profiler import parse_snapshot, to_folded_text
    st = _require_init()
    if st.core is None or not hasattr(st.core, "profiler_snapshot"):
        return {"profiler": "disabled"}
    snap = st.core.profiler_snapshot()
    if not snap:
        return {"profiler": "disabled"}
    doc = parse_snapshot(snap)
    return doc if parsed else to_folded_text(doc)


class profile:
    """Context manager running a sampling window over its body::

        with hvd.profile() as prof:
            train_some_steps()
        print(hvd.profiler.format_report(prof.result))

    On exit the window is stopped and ``prof.result`` holds the parsed
    folded-stacks document (``{"profiler": "disabled"}`` when the native
    core is absent). ``path`` writes flamegraph.pl-compatible folded lines
    there too — feed them to ``scripts/prof_report.py`` or flamegraph.pl
    directly."""

    def __init__(self, path: Optional[str] = None):
        self._path = path
        self.result: Optional[dict] = None

    def __enter__(self) -> "profile":
        prof_start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        prof_stop()
        self.result = prof_snapshot()
        if self._path and isinstance(self.result, dict) and \
                "stacks" in self.result:
            from .profiler import to_folded_text
            with open(self._path, "w") as f:
                f.write(to_folded_text(self.result))
