"""What only the SPMD program knows about itself, kept in the runtime state.

The SPMD half of two APIs that exist: the counters behind ``hvd.metrics()`` /
``hvd.metrics_dump()`` and the host spans of ``hvd.start_timeline``. One
recorder lives in the runtime state from ``hvd.init()`` to ``hvd.shutdown()``
(process mode has the native core's registry and timeline instead).

Counters, always on. JAX tells its listeners (``jax.monitoring``) whenever it
traces, lowers or compiles a function, with the function's name, and whenever
the persistent cache hits or is written; ``hvd.init`` times its own phases;
``hvd.shard_batch`` adds a call and its bytes; code that decides a tiling or
what a block keeps notes it while JAX traces it (``_TRACED``); and what the
compiler made of a step is reduced when ``hvd.compiled_step_report`` asks and
shown from then on (``_STEP_FAMILIES``). Nothing here runs on a step's path:
the listeners fire only while JAX compiles, and ``shard_batch`` pays four
integer additions and takes no lock.

Spans, only between ``start_timeline`` and ``stop_timeline``: the ``hvd.init``
phases, every compile with its function and cause, every ``shard_batch`` from
call to return and, from one watcher thread that exists only in that interval,
from return to the batch being ready on every chip. All on ``time.time_ns``,
the clock of the device trace's ``profile_start_time``, so the span file and
the ``.xplane.pb`` lie over each other by a subtraction. docs/timeline.md has
the file's format, docs/metrics.md the metric catalog.
"""

from __future__ import annotations

import collections
import json
import os
import queue
import re
import threading
import time
from typing import Optional

from . import hlo_report

# The spans a timeline keeps at most; older ones fall out. A constant, not an
# option: 65,536 spans are hours of steps at one shard_batch a step.
SPAN_RING = 65536
WATCHER_THREAD = "hvd-timeline-batch-ready"

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # A persistent-cache load happens inside this one too.
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# JAX records a miss where it writes the new entry, so a compile under the
# cache's thresholds (jax_persistent_cache_min_compile_time_secs) is neither.
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# JAX names the lowering and the compile "jit(f)", the trace "f".
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _function(fun_name: str) -> str:
    m = _WRAPPED.match(fun_name)
    return m.group(1) if m else fun_name


def _process_age_s() -> Optional[float]:
    """Seconds since the operating system started this process (Linux), or
    None. A caller may have started the backend before ``hvd.init`` (the
    first ``jax.devices()`` of a process pays for the chip's start-up), so
    the one number that holds all of start-up is measured from the process's
    own start."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


# The trace-time families, name -> (help, label names): which tiling, shapes
# or kept values a job got (``note_traced``); traces, the last one bytes.
_TRACED = {
    "hvdtpu_spmd_flash_kernel_traces_total": (
        "Times JAX traced a flash attention kernel, by kernel and the tiling "
        "the call got: block sizes, the MXU operands' dtype, query heads per "
        "K/V head, the width of a query and key head and of a value head "
        "(latent attention's differ), and where dQ is made: fused (this "
        "hvd_flash_dkdv is the whole backward pass), own (dKdV and dQ a "
        "kernel each: a K/V head's dK and dV do not fit VMEM), none (the "
        "forward).",
        ("kernel", "block_q", "block_k", "operand_dtype", "kv_group",
         "key_dim", "value_dim", "dq")),
    "hvdtpu_spmd_flash_tiles_total": (
        "Tiles of the grids of the flash attention kernels JAX traced, by "
        "kernel, the mask's kind (causal, window, block_diffusion, full), "
        "the padded length and what became of the tile: kept (computed), "
        "skipped (wholly above the diagonal), skipped_band (wholly below a "
        "window's band) or, under the block-diffusion mask, "
        "skipped_block_diffusion (every tile of the 2L x 2L rectangle that "
        "holds no kept pair, above its diagonal or below). One rectangle of "
        "(q blocks x k blocks) a trace, the same for every head.",
        ("kernel", "mask", "tiles", "seq")),
    "hvdtpu_spmd_flash_grid_steps_total": (
        "Steps a head's grid walks in the flash attention kernels JAX "
        "traced, by kernel, the mask's kind and the padded length: the "
        "length of the table of kept tiles the grid's one axis runs over, "
        "so it equals hvdtpu_spmd_flash_tiles_total{tiles=kept} (no step "
        "is a tile the mask drops).",
        ("kernel", "mask", "seq")),
    "hvdtpu_spmd_flash_pairs_total": (
        "Query-key pairs of one head in the flash attention kernels JAX "
        "traced, by kernel, the mask's kind and the padded length: computed "
        "(the pairs of the tiles the grid walks, steps x block_q x block_k) "
        "beside kept (the pairs the mask keeps, ops/flash_attention.py::"
        "Mask.kept_pairs): kept over computed is how full the computed "
        "tiles are. Only for a mask whose description holds its sequence's "
        "length (block_diffusion); the others count no pairs.",
        ("kernel", "mask", "pairs", "seq")),
    "hvdtpu_spmd_flash_layout_traces_total": (
        "Times JAX traced a call of flash_attention, by how its operands "
        "reach the kernels: rank4 (the caller asked, heads_major: q, k, v, "
        "every output and cotangent as [B, H, S, D], a transpose of the "
        "model's array that XLA folds into the layouts round the call; the "
        "attention mixer asks at two or more sequences of heads of one "
        "lane tile, the latent-attention mixer at two or more sequences) "
        "or rank3 (the default: merged to [B*H, S, D] besides, "
        "a reshape no layout crosses at a batch of two or more, so each is "
        "turned in a copy of its own), with the width of a query and key "
        "head and the batch.",
        ("layout", "head_dim", "batch")),
    "hvdtpu_spmd_head_loss_traces_total": (
        "Times JAX traced the GPT's head and loss as one rule over blocks of "
        "token rows (models/gpt.py::_head_loss), by the rows a block holds "
        "(from the rank's token count and the vocabulary alone), the blocks, "
        "the vocabulary and whether the head is the embedding's transpose.",
        ("rows_per_block", "blocks", "vocab", "tied")),
    "hvdtpu_spmd_moe_layer_traces_total": (
        "Times JAX traced an expert layer (the recomputed copy of a block "
        "counts again), by the experts it routes over, the experts per "
        "token, the size of the expert-parallel axis, the grouped matmul it "
        "uses, the experts this rank holds, the token-expert rows it "
        "gathers and multiplies at a time (all of them, or a share's "
        "window), the router's score function, whether a selection "
        "bias leans its choice, the router's kind (linear: the layer's own "
        "one matrix; mlp: the caller's, its outputs handed in; "
        "linear_early: the caller's one matrix on the block's input, before "
        "the mixer), whether it took a state from the layer before, the "
        "experts' gate (silu or relu), and the groups the router's choice "
        "is limited to and how many of them a token keeps (1 and 1: one "
        "choice over all the scores).",
        ("experts", "top_k", "ep", "grouped_matmul", "held", "rows",
         "score", "bias", "router", "state", "activation", "groups",
         "groups_kept")),
    "hvdtpu_spmd_cca_traces_total": (
        "Times JAX traced a CCA attention mixer (latent q and k mixed by two "
        "stacked causal convolutions; the recomputed copy of a block counts "
        "again), by its query heads, key/value heads, their size, the taps "
        "of the depthwise and of the grouped stage, and the dimensions of a "
        "head the rotary embedding turns.",
        ("heads", "kv_heads", "head_dim", "taps0", "taps1", "rotary_dim")),
    "hvdtpu_spmd_mla_traces_total": (
        "Times JAX traced a latent attention (MLA) mixer (keys and values "
        "from a normed latent, one rotary key a token for all heads; the "
        "recomputed copy of a block counts again), by its heads, the "
        "no-position and the rotary part of a query/key head, a value "
        "head's size, the key/value latent's rank and the query latent's "
        "(none: the query is projected straight from the stream), and the "
        "gate on the attention's output (none, or head: one sigmoid gate a "
        "head before the output projection).",
        ("heads", "nope_dim", "rope_dim", "value_dim", "kv_rank", "q_rank",
         "gate")),
    "hvdtpu_spmd_kda_traces_total": (
        "Times JAX traced a chunked Kimi-delta-attention scan (a delta-rule "
        "state that decays a key channel; the recomputed copy of a block "
        "counts again), by its heads, a head's key and value size, the "
        "chunk, the sub-block its decayed products are made in, and the "
        "bound its gate lies above.",
        ("heads", "key_dim", "value_dim", "chunk", "sub_chunk",
         "lower_bound")),
    "hvdtpu_spmd_ssm_layer_traces_total": (
        "Times JAX traced a chunked state-space scan (the recomputed copy of "
        "a block counts again), by its heads, their size, the state's size, "
        "the groups that share B and C, and the chunk.",
        ("heads", "head_dim", "state", "groups", "chunk")),
    "hvdtpu_spmd_gdn_layer_traces_total": (
        "Times JAX traced a chunked gated-delta-rule scan (the recomputed "
        "copy of a block counts again), by its key heads, value heads, their "
        "sizes and the chunk, what the recurrence over chunks ran as, over "
        "how many chunks a sequence, what the writing strength beta "
        "lies under (1: a sigmoid; 2: twice one, negative eigenvalues), and "
        "who L2-normalises q and k (kernel: the chunk-local kernels, in "
        "VMEM; caller: they come normed).",
        ("key_heads", "value_heads", "key_dim", "value_dim", "chunk",
         "recurrence", "chunks", "beta_max", "qk_norm")),
    "hvdtpu_spmd_ssd_kernel_traces_total": (
        "Times JAX traced one of the state-space scan's within-chunk "
        "kernels, by kernel and the tiling the call got: the chunk, the "
        "heads a grid cell holds, the MXU operands' dtype.",
        ("kernel", "chunk", "heads_per_block", "operand_dtype")),
    "hvdtpu_spmd_s6_traces_total": (
        "Times JAX traced a Mamba-1 mixer (the recomputed copy of a block "
        "counts again), by its channels, the states a channel, the rank of "
        "the step size's projection, the convolution's taps, and whether "
        "the layer hands its scan's output on to later layers.",
        ("channels", "state", "dt_rank", "conv", "publishes")),
    "hvdtpu_spmd_s6_kernel_traces_total": (
        "Times JAX traced one of the selective scan's kernels (the state of "
        "a block of channels in VMEM across a grid axis over tokens), by "
        "kernel and what the call got: the tokens and channels it walks "
        "(padded to whole blocks), the states a channel, the tokens a grid "
        "cell, the dtype of u and y.",
        ("kernel", "tokens", "channels", "state", "chunk", "operand_dtype")),
    "hvdtpu_spmd_diff_attention_traces_total": (
        "Times JAX traced a differential attention mixer (two softmax maps "
        "a pair of heads, two calls of the attention kernels; the recomputed "
        "copy of a block counts again), by its query pairs, key/value "
        "pairs, a key head's size (a value head is twice that), the window "
        "(0: none), and whether it reads an earlier layer's keys and values.",
        ("pairs", "kv_pairs", "head_dim", "window", "cross")),
    "hvdtpu_spmd_shared_values_total": (
        "Times JAX traced a stack in which a layer hands a value on to later "
        "layers beside the stream, by the value's name, the layer that "
        "publishes it and how many later layers read it.",
        ("value", "producer", "readers")),
    "hvdtpu_spmd_loop_passes_total": (
        "Times JAX traced a looped stack (models/gpt.py::_passes: the same "
        "layers run more than once a step, GPTConfig.loop_passes), by its "
        "passes and the layers a pass runs: passes x layers block "
        "applications a forward pass. A stack that runs once counts nothing.",
        ("passes", "layers")),
    "hvdtpu_spmd_gdn_kernel_traces_total": (
        "Times JAX traced one of the gated delta rule's kernels (the "
        "chunk-local pair, the recurrence over chunks' pair), by kernel and "
        "the tiling the call got: the chunk, the value heads a grid cell "
        "holds, the MXU operands' dtype, and the lanes a key head and a "
        "value head occupy in the kernel (their sizes rounded up to the "
        "lane width: more than key_dim or value_dim is zeros).",
        ("kernel", "chunk", "heads_per_block", "operand_dtype", "key_lanes",
         "value_lanes")),
    "hvdtpu_spmd_conv_kernel_traces_total": (
        "Times JAX traced one of the kernels of the causal depthwise "
        "convolution in front of a scan (taps, bias and SiLU in one pass), "
        "by kernel and what the call got from its shapes: the channels, the "
        "taps, whether a bias is added, the operand's dtype, the tokens by "
        "channels a grid cell holds, and which of the two is on the lanes.",
        ("kernel", "channels", "taps", "bias", "operand_dtype", "tile",
         "minor")),
    "hvdtpu_spmd_cca_kernel_traces_total": (
        "Times JAX traced one of the kernels of a CCA mixer's mix (both "
        "convolutions, the q/k means, the L2 norms under the key "
        "temperature and the rotary embedding in one pass over the latent a "
        "direction), by kernel and the cut the call got from its shapes: "
        "the tokens a grid cell and a piece of its walk hold, the query and "
        "key heads, the lanes a head occupies (its size rounded up to the "
        "lane width: more than head_dim is zeros), the dimensions of a head "
        "the rotary embedding turns and the operand's dtype.",
        ("kernel", "tokens", "rows", "heads", "kv_heads", "head_lanes",
         "rotary_dim", "operand_dtype")),
    "hvdtpu_spmd_remat_saved_bytes_total": (
        "Bytes a checkpointed block hands from its forward to its backward "
        "pass beside its input, by remat mode and the name the value "
        "carries; one block for each that JAX splits (layers alike share "
        "one).", ("mode", "name")),
}

# ---- what the compiler made of a step (hvd.compiled_step_report) -----------


def _by_kind(report: dict) -> dict:
    """kind -> (instructions, their result bytes or None) of a report."""
    remat, copies = report["rematerialized"], report["parameter_copies"]
    return {"rematerialized": (len(remat), sum(r["bytes"] for r in remat)),
            "parameter_copy": (copies["count"], copies["bytes"]),
            "while": (report["whiles"], None),
            **{kind: (n, None) for kind, n in report["collectives"].items()}}


# name -> (help, a report's samples as [(labels, value)]); gauges, by function
_STEP_FAMILIES = {
    "hvdtpu_spmd_step_memory_bytes": (
        "The compiler's account of a step's memory on one device "
        "(memory_analysis()): arguments, outputs, aliased (outputs written "
        "where donated arguments were), temporaries, generated_code.",
        lambda r: [({"kind": k}, v) for k, v in r["memory_bytes"].items()]),
    "hvdtpu_spmd_step_instructions": (
        "Instructions of the compiled step that the program did not ask "
        "for or that cross chips: rematerialized (made again by the "
        "compiler when short of memory), parameter_copy (copies of the "
        "step's arguments), while, and collectives by kind after the "
        "compiler's combiner.",
        lambda r: [({"kind": k}, n) for k, (n, _) in _by_kind(r).items()]),
    "hvdtpu_spmd_step_instruction_bytes": (
        "Result bytes of the rematerialized instructions and of the "
        "parameter copies.",
        lambda r: [({"kind": k}, b) for k, (_, b) in _by_kind(r).items()
                   if b is not None]),
    "hvdtpu_spmd_step_kernels": (
        "Mosaic kernels in the compiled step, by the name the program or "
        "XLA gave them, the pass they run in (forward, recomputation, "
        "backward; none: no name of the program places the kernel) and what "
        "placed them there (own: the kernel's own op_name; operands: the "
        "name of the instruction that makes its rows, for a kernel XLA "
        "names itself).",
        lambda r: [(dict(zip(("kernel", "pass", "placed_by"), key)), n)
                   for key, n in sorted(collections.Counter(
                       (c["kernel"], c["pass"], c["placed_by"])
                       for c in r["kernel_calls"]).items())]),
}


class SpmdRecorder:
    def __init__(self):
        # (function, stage) -> [count, seconds]
        self.compiles: dict = collections.defaultdict(lambda: [0, 0.0])
        self.cache_hits = 0
        self.cache_misses = 0
        self.init_phases: list = []     # (phase, start_ns, end_ns)
        self.init_done_process_s: Optional[float] = None
        self.placed_calls = 0
        self.placed_bytes = 0
        self.placed_leaves = {"flat": 0, "direct": 0}
        # family of _TRACED -> its label values -> traces (or bytes)
        self.traced: dict = {name: collections.Counter() for name in _TRACED}
        # function -> (its traced shapes, report): compiled_step_report's
        self.step_reports: dict = {}
        # function -> the argument signatures run_step has traced it with
        self._signatures: dict = {}
        self._cause: dict = {}          # function -> cause of the next compile
        self._lock = threading.Lock()   # the listeners', at compile time only
        # The timeline, None when none runs.
        self.spans: Optional[collections.deque] = None
        self._timeline_path = ""        # the device trace: this + ".xplane"
        self._trace_started_ns = 0
        self._pending: Optional[queue.SimpleQueue] = None
        self._watcher: Optional[threading.Thread] = None

    # ---- lifecycle (hvd.init / hvd.shutdown) -----------------------------

    def phase(self, name: str, start_ns: int, end_ns: int) -> None:
        self.init_phases.append((name, start_ns, end_ns))

    def start(self) -> None:
        from jax import monitoring
        self.init_done_process_s = _process_age_s()
        monitoring.register_event_time_span_listener(self._on_time_span)
        monitoring.register_event_listener(self._on_event)

    def stop(self) -> None:
        from jax import monitoring
        if self.spans is not None:
            self.stop_timeline()
        monitoring.unregister_event_time_span_listener(self._on_time_span)
        monitoring.unregister_event_listener(self._on_event)

    # ---- what JAX and the program report ---------------------------------

    def _on_time_span(self, event: str, start: float, end: float,
                      fun_name: str = "", **_) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        function = _function(fun_name)
        with self._lock:
            entry = self.compiles[(function, stage)]
            entry[0] += 1
            entry[1] += end - start
            cause = self._cause.get(function, "")
            if stage == "backend_compile":
                self._cause.pop(function, None)
        if self.spans is not None:
            self.span(f"compile/{stage}", int(start * 1e9), int(end * 1e9),
                      function=function, cause=cause)

    def _on_event(self, event: str, **_) -> None:
        with self._lock:
            if event == _CACHE_HIT:
                self.cache_hits += 1
            elif event == _CACHE_MISS:
                self.cache_misses += 1

    def note_trace(self, function: str, signature) -> None:
        """``run_step`` calls this while JAX traces the step's body: why this
        trace, and the compile that follows it, happened."""
        with self._lock:
            seen = self._signatures.setdefault(function, set())
            self._cause[function] = (
                "first call" if not seen
                else "new shardings" if signature in seen else "new shapes")
            seen.add(signature)

    def note_traced(self, family: str, amount: int = 1, **labels) -> None:
        """Code that JAX is tracing calls this, never a step: one more trace
        (or ``amount`` more bytes) of a family of ``_TRACED``."""
        key = tuple(labels[name] for name in _TRACED[family][1])
        with self._lock:
            self.traced[family][key] += amount

    def step_report(self, function: str, traced, compile_) -> dict:
        """``hvd.compiled_step_report``'s answer for ``function`` as last
        ``traced``: kept, or made now of what ``compile_()`` returns."""
        with self._lock:
            have = self.step_reports.get(function)
        if have is None or have[0] is not traced:
            t0 = time.perf_counter()
            report = dict(hlo_report.compiled_report(compile_()),
                          function=function)
            report["seconds"] = time.perf_counter() - t0
            with self._lock:
                have = self.step_reports[function] = (traced, report)
        return have[1]

    def note_placed(self, nbytes: int, leaves: int, flat: int) -> None:
        self.placed_calls += 1
        self.placed_bytes += nbytes
        self.placed_leaves["flat"] += flat
        self.placed_leaves["direct"] += leaves - flat

    # ---- hvd.metrics() ---------------------------------------------------

    def families(self) -> dict:
        """The counters in the shape ``observability.parse_prometheus_text``
        gives (docs/metrics.md, "SPMD mode")."""
        def family(kind: str, help_: str, samples: list) -> dict:
            return {"type": kind, "help": help_, "samples": samples}

        with self._lock:
            compiles = sorted(self.compiles.items())
            hits, misses = self.cache_hits, self.cache_misses
            traced = {name: sorted(samples.items())
                      for name, samples in self.traced.items()}
            reports = sorted(self.step_reports.items())
        counts, seconds = [], []
        for (function, stage), (count, secs) in compiles:
            labels = {"function": function, "stage": stage}
            counts.append(("", labels, float(count)))
            seconds.append(("", labels, secs))
        out = {
            "hvdtpu_spmd_compiles_total": family(
                "counter", "Times JAX traced, lowered or compiled (or loaded "
                "from the persistent cache) a function, by function and "
                "stage.", counts),
            "hvdtpu_spmd_compile_seconds_total": family(
                "counter", "Seconds of those, by function and stage.",
                seconds),
            "hvdtpu_spmd_compile_cache_hits_total": family(
                "counter", "Executables loaded from the persistent "
                "compilation cache.", [("", {}, float(hits))]),
            "hvdtpu_spmd_compile_cache_misses_total": family(
                "counter", "Executables compiled and written to the "
                "persistent compilation cache.", [("", {}, float(misses))]),
            "hvdtpu_spmd_init_seconds": family(
                "gauge", "Seconds of each phase of hvd.init(): backend (the "
                "first jax.devices(), the chip's start-up unless the caller "
                "made it before), mesh, compile_cache.",
                [("", {"phase": p}, (b - a) * 1e-9)
                 for p, a, b in self.init_phases]),
            "hvdtpu_spmd_shard_batch_calls_total": family(
                "counter", "Calls of hvd.shard_batch.",
                [("", {}, float(self.placed_calls))]),
            "hvdtpu_spmd_shard_batch_bytes_total": family(
                "counter", "Host bytes hvd.shard_batch was given to place.",
                [("", {}, float(self.placed_bytes))]),
            "hvdtpu_spmd_shard_batch_leaves_total": family(
                "counter", "Leaves hvd.shard_batch placed, by the path they "
                "took: flat (a host array of rank 3 or more crossed as its "
                "[N, rest] view and took its shape on the device) or direct "
                "(jax.device_put on the leaf as it is).",
                [("", {"path": path}, float(n))
                 for path, n in self.placed_leaves.items()]),
        }
        for name, (help_, labels) in _TRACED.items():
            out[name] = family("counter", help_, [
                ("", dict(zip(labels, map(str, key))), float(count))
                for key, count in traced[name]])
        if reports:     # only once compiled_step_report has been asked
            for name, (help_, samples_of) in _STEP_FAMILIES.items():
                out[name] = family("gauge", help_, [
                    ("", {"function": function, **labels}, float(value))
                    for function, (_, report) in reports
                    for labels, value in samples_of(report)])
        if self.init_done_process_s is not None:
            out["hvdtpu_spmd_init_done_process_seconds"] = family(
                "gauge", "Seconds from the start of the process to the "
                "return of hvd.init().",
                [("", {}, self.init_done_process_s)])
        return out

    # ---- hvd.start_timeline / hvd.stop_timeline --------------------------

    def span(self, name: str, start_ns: int, end_ns: int, **args) -> None:
        spans = self.spans
        if spans is not None:
            spans.append((name, start_ns, end_ns, args))

    def start_timeline(self, path: str) -> None:
        import jax

        if self.spans is not None:
            raise RuntimeError("a timeline is already running")
        # The device planes alone. With the host tracer on, at its default
        # level (2) or at 1, the runtime's host-side layout change of a uint8
        # image batch writes a million Transpose events a batch and runs five
        # to ten times slower: 20 ResNet-50 steps gave a 627 MB trace that
        # took 85 s to stop, with the chip waiting for a feed no untraced run
        # has (PERF.md, "Reading the trace"). The host's side is in the spans.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        self._timeline_path = path
        self.spans = collections.deque(maxlen=SPAN_RING)
        self._pending = queue.SimpleQueue()
        self._watcher = threading.Thread(
            target=self._watch, args=(self._pending,),
            name=WATCHER_THREAD, daemon=True)
        self._watcher.start()
        self._trace_started_ns = time.time_ns()
        jax.profiler.start_trace(path + ".xplane", profiler_options=options)

    def watch_batch(self, placed, returned_ns: int) -> None:
        pending = self._pending
        if pending is not None:
            pending.put((placed, returned_ns))

    def _watch(self, pending: "queue.SimpleQueue") -> None:
        import jax

        while True:
            item = pending.get()
            if item is None:
                return
            placed, returned_ns = item
            try:
                jax.block_until_ready(placed)
            except RuntimeError:    # a donated batch: a step took it
                continue
            self.span("batch_ready", returned_ns, time.time_ns())

    def stop_timeline(self) -> None:
        import jax

        if self.spans is None:
            return
        try:
            jax.profiler.stop_trace()
        finally:
            self._pending.put(None)
            self._watcher.join()
            spans, self.spans = self.spans, None
            self._pending = self._watcher = None
        xplane, profile_start_ns = _read_profile_start(
            self._timeline_path + ".xplane")
        events = [
            {"name": name, "ph": "X", "pid": os.getpid(), "tid": 0,
             "ts": start / 1e3, "dur": (end - start) / 1e3,
             "args": dict(args, start_ns=start, end_ns=end)}
            for name, start, end, args in
            [(f"init/{p}", a, b, {}) for p, a, b in self.init_phases]
            + list(spans)]
        with open(self._timeline_path, "w") as f:
            json.dump({
                "traceEvents": events, "displayTimeUnit": "ms",
                "metadata": {
                    "clock": "time.time_ns: ts and dur in us, args.start_ns "
                             "and args.end_ns in ns since the epoch",
                    # Subtract from a span to put it on the device trace's
                    # clock (ns since the profile started).
                    "profile_start_time": profile_start_ns
                    or self._trace_started_ns,
                    "profile_start_time_from": "xplane" if profile_start_ns
                    else "time.time_ns at start_timeline",
                    "xplane": xplane}}, f)


def _read_profile_start(trace_dir: str) -> tuple:
    """(path of the newest ``.xplane.pb`` under ``trace_dir``, its
    ``profile_start_time`` in ns since the epoch); None where absent."""
    import glob

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None, None
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name == "Task Environment":
            return found[-1], dict(plane.stats).get("profile_start_time")
    return found[-1], None
