#!/usr/bin/env python3
"""Compile a benchmark cell's real training step for a v5e that is described
and not attached, in the CPU sandbox: a sha256 of the lowered StableHLO and
one of the compiled program, and what the program's own report says of the
executable (``hvd.compiled_step_report``'s reducer, so the sandbox and the
chip count alike): its memory, the Mosaic kernels it holds by name and by the
pass each runs in (``kernel_calls``), what the compiler made again and which
arguments it copies; and, from the trace,
the bytes its checkpointed blocks keep by name (``remat_saved_bytes``: the
job's ``hvdtpu_spmd_remat_saved_bytes_total``) and the layout in which its
flash kernels get their operands (``flash_layouts``: the job's
``hvdtpu_spmd_flash_layout_traces_total``). Nothing runs and no
time is taken; a compile that passes is not a chip run.

    python3 scripts/aot_step.py starcoder2-3b_s4096 olmoe-1b-7b_s4096

The step is the job's own (``benchmarks/jobs/*.py``: ``hvd.run_step`` over
``DistributedOptimizer``, state donated), lowered on shapes alone. One JSON
line a cell; ``--repo DIR`` compiles another checkout's program (a copy of
the parent commit) under this script and this checkout's reducer.
"""

from __future__ import annotations

import argparse
import base64
import collections
import hashlib
import importlib
import importlib.util
import json
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 2.0 ** 30


# A Mosaic kernel's module in the StableHLO's text and in the compiled HLO's.
_KERNEL_BODY = re.compile(
    r'(?<=\\22body\\22: \\22)[A-Za-z0-9+/=]+(?=\\22)'
    r'|(?<="body":")[A-Za-z0-9+/=]+(?=")')
_METADATA = re.compile(r", metadata=\{[^{}]*\}")


def this_checkouts_reducer():
    """``horovod_tpu/hlo_report.py`` of this script's checkout, whatever
    ``--repo`` put first on the path (the module imports nothing of the
    package)."""
    spec = importlib.util.spec_from_file_location(
        "aot_step_reducer", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "horovod_tpu", "hlo_report.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def program_text(text: str) -> str:
    """A program's text without source locations. The StableHLO has none of
    its own, but a Mosaic kernel travels in it as its module's bytes, which
    hold the file and line of every operation of the kernel's source: each
    is replaced by the module's assembly printed without them."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    context = mlir.make_ir_context()
    tpu.register_dialect(context)
    context.allow_unregistered_dialects = True

    def assembly(match):
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(0)))
            return module.operation.get_asm(enable_debug_info=False)

    return _KERNEL_BODY.sub(assembly, text)


def compiled_text(text: str) -> str:
    """The compiled HLO without what names and locates and does not compute:
    each instruction's ``metadata``, the stack-frame tables ahead of the
    first computation, the kernels' source locations. Equal on two
    checkouts, they differ in metadata alone."""
    head, body = text.split("\n", 1)
    body = body[re.search(r"^(%|ENTRY )", body, re.M).start():]
    return program_text(_METADATA.sub("", head + "\n" + body))


def remat_saved_bytes(hvd) -> dict:
    """``hvdtpu_spmd_remat_saved_bytes_total`` by name, as this process has
    counted it so far (``models/gpt.py::_full_policy``, at trace time)."""
    family = hvd.metrics().get("hvdtpu_spmd_remat_saved_bytes_total", {})
    return {labels["name"]: value
            for _, labels, value in family.get("samples", ())}


def flash_layouts(hvd) -> dict:
    """``hvdtpu_spmd_flash_layout_traces_total`` as ``"rank4 d192 b2" ->
    calls traced``, so far: how q, k and v reach the flash kernels, which is
    their caller's word (``flash_attention(heads_major=)``)."""
    family = hvd.metrics().get("hvdtpu_spmd_flash_layout_traces_total", {})
    return {"{layout} d{head_dim} b{batch}".format(**labels): value
            for _, labels, value in family.get("samples", ())}


def counted_since(before: dict, now: dict) -> dict:
    """What one cell's trace added to a counter this process keeps."""
    return {name: value - before.get(name, 0) for name, value in now.items()
            if value != before.get(name, 0)}


def calls_by_pass(kernel_calls: list) -> dict:
    """kernel -> pass -> calls in the compiled step."""
    out: dict = {}
    for call in kernel_calls:
        out.setdefault(call["kernel"], collections.Counter())[
            call["pass"]] += 1
    return out


def compile_cell(name: str, root: str, hlo_dir: str | None = None) -> dict:
    import jax
    from jax.sharding import NamedSharding
    import horovod_tpu as hvd

    from benchmarks import run

    bench = run.load_json(root, "BENCHMARK.json")
    cell = run.find(bench["workloads"], name, "workload")
    config = run.load_json(root, run.find(
        bench["configs"], cell["config"], "config")["file"])
    traffic = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    job = importlib.import_module(
        "benchmarks.jobs." + config["job"]).Job(config, traffic, seed=0)

    replicated = NamedSharding(hvd.mesh(), hvd.REPLICATED)

    def shapes(f, *args):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=replicated),
            jax.eval_shape(f, *args))

    params = shapes(job.init_params, jax.random.PRNGKey(0))
    opt_state = shapes(job.opt.init, params)
    data = tuple(
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(
            hvd.mesh(), hvd.batch_spec(0)))
        for x in job.host_batches(1)[0])
    kept_before, layouts_before = remat_saved_bytes(hvd), flash_layouts(hvd)
    lowered = job.step.lower(params, opt_state, data)
    kept = counted_since(kept_before, remat_saved_bytes(hvd))
    layouts = counted_since(layouts_before, flash_layouts(hvd))
    t0 = time.time()
    compiled = lowered.compile()
    seconds = time.time() - t0
    text = compiled.as_text()
    report = this_checkouts_reducer().compiled_report(compiled)
    m = report["memory_bytes"]
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, name + ".hlo.txt"), "w") as f:
            f.write(text)
    return {
        "cell": name, "compile_s": round(seconds, 1),
        # Equal on two checkouts, the step is the same program on both.
        "stablehlo_sha256": hashlib.sha256(
            program_text(lowered.as_text()).encode()).hexdigest(),
        # Equal too, the compiler made the same of it.
        "compiled_sha256": hashlib.sha256(
            compiled_text(text).encode()).hexdigest(),
        "instructions": report["instructions"],
        "arguments_gib": round(m["arguments"] / GIB, 3),
        "temporaries_gib": round(m["temporaries"] / GIB, 3),
        # What the step holds at once; outputs that alias donated
        # arguments are written where those were read.
        "total_gib": round((m["arguments"] + m["temporaries"] + m["outputs"]
                            - m["aliased"]) / GIB, 3),
        "calls": calls_by_pass(report["kernel_calls"]),
        # Those the report placed by what they read: XLA's own kernels,
        # which carry no name of the program's.
        "calls_placed_by_operands": collections.Counter(
            call["kernel"] for call in report["kernel_calls"]
            if call["placed_by"] == "operands"),
        "rematerialized": report["rematerialized"],
        "parameter_copies": report["parameter_copies"],
        # What the checkpointed blocks of this cell's trace kept, by name:
        # one block's bytes for each that JAX split (layers alike share one).
        "remat_saved_bytes": kept,
        # How this cell's trace handed q, k, v to the flash kernels.
        "flash_layouts": layouts,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cells", nargs="+")
    parser.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("--hlo", metavar="DIR", help="write each cell's "
                        "compiled HLO text to DIR/<cell>.hlo.txt (which "
                        "copies XLA put around a kernel, and in what layout)")
    args = parser.parse_args()
    root = os.path.abspath(args.repo)
    sys.path.insert(0, root)

    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    import horovod_tpu as hvd
    from horovod_tpu.compression import quantize
    from horovod_tpu.ops import pallas_util
    # Through Mosaic: the kernel layer's platform test, and what a ``--repo``
    # from before PR 43 asked in its place.
    pallas_util.on_tpu = quantize._pallas_backend_enabled = lambda *_: True
    hvd.init(devices=topo.devices[:1])
    for name in args.cells:
        print(json.dumps(compile_cell(name, root, args.hlo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
