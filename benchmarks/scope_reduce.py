"""A device trace split by what the program named: scopes and kernels.

The program compiles under ``jax.named_scope``s (``hvd_exchange``,
``hvd_optimizer``, ``layer<i>/attn`` ...) and names its Pallas kernels; JAX
adds ``jvp(`` (forward), ``transpose(jvp(`` (backward) and
``rematted_computation`` (recomputation). All of it reaches the compiled
program as each instruction's ``op_name``, and a kernel's name becomes its
instruction's name, which ``trace_reduce.Op.name`` holds.

Where the names are in a v5e's trace (read by hand, my chip run, PR 23). A
device event's name is the instruction *without* its metadata, and
``ProfileData`` shows only an event's own statistics, so ``Op.tags`` holds no
``op_name``. It is in the ``.xplane.pb`` all the same, in two places this
module reads from the file itself with a reader of the protobuf wire format
(no schema is installed that does not import TensorFlow):

* each device plane's event metadata carries ``display_name`` (the
  instruction's name) and the statistic ``tf_op`` (``op_name`` and a colon);
* the plane ``/host:metadata`` carries each program's ``Hlo Proto``: every
  instruction of every computation, fused ones included, with its metadata.

**A fusion is one kernel and carries one ``op_name``, its root's**, and its
time is never split. XLA fuses a whole optimizer update (AdamW, momentum)
into the ``add`` of the job's own ``optax.apply_updates``, which lies outside
the program's scopes, so by the root alone the optimizer would read as
unscoped. Where the file holds the HLO, a fusion whose root has no class
therefore goes to the class most of its fused instructions have; a fusion
whose root has one keeps it, whatever it fuses (XLA copies cheap forward
operations into backward kernels: they run in the backward pass).

An instruction's class is the first rule that holds of its ``op_name``:

    hvd_exchange, or psum_invariant under transpose(jvp(   exchange
    hvd_optimizer                                          optimizer
    rematted_computation                                   recomputation
    transpose(jvp(                                         backward
    jvp(                                                   forward
    anything else, or none                                 unscoped

``psum_invariant`` is the all-reduce autodiff itself inserts for the gradient
of a replicated parameter under ``shard_map``: the data-parallel gradient
exchange of a step that differentiates against replicated parameters, as the
benchmark's jobs do (``DistributedOptimizer`` then only normalises, under
``hvd_exchange``).

``python benchmarks/scope_reduce.py <file.xplane.pb>`` prints chip 0's time by
class and by scope, and the largest unscoped operations, over the whole file.
"""

from __future__ import annotations

import collections
import functools
import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402

CLASSES = ("exchange", "optimizer", "recomputation", "backward", "forward",
           "unscoped")
HLO_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
OP_NAME_STAT = "tf_op"
# Where an event metadata has no display name, its name is the whole
# instruction as XLA prints it.
_INSTRUCTION_NAME = re.compile(r"^%?(\S+) = ")


def classify(op_name: str) -> str:
    if "hvd_exchange" in op_name or (
            "psum_invariant" in op_name and "transpose(jvp(" in op_name):
        return "exchange"
    if "hvd_optimizer" in op_name:
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recomputation"
    if "transpose(jvp(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "unscoped"


# ---- the protobuf wire format, as far as these files need it --------------

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {kind} is not in these files")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _varints(values) -> list:
    """A repeated integer field, packed (one length-delimited value) or
    not."""
    out = []
    for value in values:
        if isinstance(value, int):
            out.append(value)
            continue
        i = 0
        while i < len(value):
            item, i = _varint(value, i)
            out.append(item)
    return out


def _map_entries(views):
    """A ``map<int64, Message>`` field: key -> the message's bytes."""
    for view in views:
        entry = dict(_fields(view))
        yield entry.get(1, 0), entry[2]


# Field numbers, from tsl/profiler/protobuf/xplane.proto and
# xla/service/hlo.proto (the textproto fixture under tests/data goes through
# JAX's own schema, and test_scope_reduce.py reads a compiled module's proto).
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_XEVENTMETADATA_NAME, _XEVENTMETADATA_DISPLAY_NAME = 2, 4
_XEVENTMETADATA_STATS = 5
_XSTAT_METADATA_ID, _XSTAT_STR, _XSTAT_BYTES, _XSTAT_REF = 1, 5, 6, 7
_XSTATMETADATA_NAME = 2
_HLOPROTO_MODULE = 1
_HLOMODULE_COMPUTATIONS = 3
_HLOCOMPUTATION_INSTRUCTIONS, _HLOCOMPUTATION_ID = 2, 5
_HLOINSTRUCTION_NAME, _HLOINSTRUCTION_OPCODE = 1, 2
_HLOINSTRUCTION_METADATA, _HLOINSTRUCTION_CALLED = 7, 38
_OPMETADATA_OP_NAME = 2


def _grouped(view) -> dict:
    out: dict = collections.defaultdict(list)
    for number, value in _fields(view):
        out[number].append(value)
    return out


def _planes(data) -> list:
    """``(name, [event metadata], {stat id: stat name})`` of each plane."""
    out = []
    for number, plane in _fields(data):
        if number != _XSPACE_PLANES:
            continue
        p = _grouped(plane)
        stat_names = {
            key: _text(dict(_fields(meta)).get(_XSTATMETADATA_NAME, b""))
            for key, meta in _map_entries(p[_XPLANE_STAT_METADATA])}
        out.append((_text(p[_XPLANE_NAME][0]) if p[_XPLANE_NAME] else "",
                    [meta for _, meta in
                     _map_entries(p[_XPLANE_EVENT_METADATA])], stat_names))
    return out


def _stats(event_metadata, stat_names: dict) -> dict:
    """stat name -> str or memoryview, of one event metadata."""
    out = {}
    for stat in event_metadata[_XEVENTMETADATA_STATS]:
        s = dict(_fields(stat))
        name = stat_names.get(s.get(_XSTAT_METADATA_ID))
        if _XSTAT_STR in s:
            out[name] = _text(s[_XSTAT_STR])
        elif _XSTAT_REF in s:       # a string kept once, as a stat's name
            out[name] = stat_names.get(s[_XSTAT_REF], "")
        elif _XSTAT_BYTES in s:
            out[name] = s[_XSTAT_BYTES]
    return out


def hlo_op_names(hlo_module) -> dict:
    """instruction name -> ``(own op_name, [op_names of the instructions it
    fuses])`` for every instruction of a serialized ``HloModuleProto``."""
    computations, instructions = {}, []
    for comp in _grouped(hlo_module)[_HLOMODULE_COMPUTATIONS]:
        c = _grouped(comp)
        rows = []
        for ins in c[_HLOCOMPUTATION_INSTRUCTIONS]:
            g = _grouped(ins)
            meta = g[_HLOINSTRUCTION_METADATA]
            op_name = _text(dict(_fields(meta[0])).get(
                _OPMETADATA_OP_NAME, b"")) if meta else ""
            rows.append((_text(g[_HLOINSTRUCTION_NAME][0]),
                         _text(g[_HLOINSTRUCTION_OPCODE][0]), op_name,
                         _varints(g[_HLOINSTRUCTION_CALLED])))
        computations[c[_HLOCOMPUTATION_ID][0] if c[_HLOCOMPUTATION_ID]
                     else 0] = rows
        instructions += rows
    return {
        name: (op_name, [inner for called_id in called
                         for _, _, inner, _ in computations.get(called_id, [])
                         if inner] if opcode == "fusion" else [])
        for name, opcode, op_name, called in instructions}


def class_of(op_name: str, fused: list) -> str:
    """The class of an instruction: its own ``op_name``'s, and for a fusion
    whose root has none, the class most of its fused instructions have (the
    earlier in ``CLASSES`` on a tie; constants and broadcasts have none)."""
    own = classify(op_name)
    if own != "unscoped":
        return own
    votes = collections.Counter(classify(n) for n in fused)
    del votes["unscoped"]
    return max(CLASSES, key=lambda c: votes[c]) if votes else own


@functools.lru_cache(maxsize=4)
def program_names(path: str) -> dict:
    """instruction name -> ``(op_name, class)`` for the programs in a trace
    file: from the HLO where the file holds it, else from the device events'
    own ``tf_op`` (a fusion then goes by its root)."""
    with open(path, "rb") as f:
        planes = _planes(memoryview(f.read()))
    out: dict = {}
    for name, event_metadata, stat_names in planes:
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        for meta in event_metadata:
            m = _grouped(meta)
            op_name = _stats(m, stat_names).get(OP_NAME_STAT)
            if op_name is None:
                continue
            shown = m[_XEVENTMETADATA_DISPLAY_NAME] or m[_XEVENTMETADATA_NAME]
            hit = _INSTRUCTION_NAME.match(_text(shown[0]))
            instruction = hit.group(1) if hit else _text(shown[0])
            op_name = op_name.rpartition(":")[0] or op_name
            out[instruction] = (op_name, classify(op_name))
    for name, event_metadata, stat_names in planes:
        if name != HLO_PLANE:
            continue
        for meta in event_metadata:
            proto = _stats(_grouped(meta), stat_names).get(HLO_STAT)
            if proto is None:
                continue
            module = dict(_fields(proto)).get(_HLOPROTO_MODULE)
            for instruction, (op_name, fused) in hlo_op_names(module).items():
                out[instruction] = (op_name, class_of(op_name, fused))
    return out


def newest_xplane() -> str | None:
    """The trace this process has just written: ``run.py`` puts it under
    ``chiprun_out/trace/<cell>/`` and hands the readers no path."""
    found = glob.glob(os.path.join(ROOT, "chiprun_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


# ---- what the readers read -------------------------------------------------

def class_seconds(trace: trace_reduce.Trace, names: dict) -> dict:
    """class -> summed durations of chip 0's operations inside the window.
    An operation the file gives no ``op_name`` for is unscoped."""
    lo, hi = trace_reduce.window_of(trace)
    out = dict.fromkeys(CLASSES, 0.0)
    for op in trace_reduce.first_device(trace):
        cls = names.get(op.name, ("", "unscoped"))[1]
        out[cls] += trace_reduce.total(
            trace_reduce.clip([(op.start, op.end)], lo, hi))
    return out


def class_ms(ctx, cls: str):
    """A class's device time per traced step in ms: 0.0 where no operation
    has the class (XLA may fuse a whole part of the program into kernels of
    another); None where there is no device trace or the file names no
    operation of the trace. A program without the program's scopes still has
    JAX's own ``jvp(``."""
    if not ctx.has_device_trace():
        return None
    path = newest_xplane()
    names = program_names(path) if path else {}
    if not any(op.name in names
               for op in trace_reduce.first_device(ctx.trace)):
        return None
    return 1e3 * class_seconds(ctx.trace, names)[cls] / ctx.steps_traced


def kernel_ms(ctx, kernel: str):
    """Device time per traced step of the operations the program named
    ``kernel`` (XLA numbers them: ``hvd_flash_fwd.3``); None where the trace
    has none."""
    if not ctx.has_device_trace():
        return None
    ms = ctx.op_ms_per_step(rf"^{re.escape(kernel)}(\.\d+)?$")
    return ms or None


def scope_of(op_name: str) -> list:
    """The scopes in an ``op_name``, outermost first, without JAX's wrappers
    and the primitive: ``jit(step)/transpose(jvp(layer0))/jvp(layer0)/
    checkpoint/rematted_computation/attn/dot_general`` -> ``["layer0",
    "attn"]``. ``jnp.einsum`` and ``jax.nn`` functions add scopes of their
    own (``bse,ev->bsv``, ``log_softmax``) under the program's."""
    parts: list = []
    for part in op_name.split("/")[1:-1]:
        while True:
            m = re.match(r"^(?:transpose|jvp|jit|pjit)\((.*)\)$", part)
            if not m:
                break
            part = m.group(1)
        if part and part not in ("shard_map", "checkpoint", "remat2",
                                 "rematted_computation") \
                and (not parts or parts[-1] != part):
            parts.append(part)
    return parts


def describe(path: str, top: int = 48) -> str:
    trace = trace_reduce.read_xplane(path, {})
    names = program_names(path)
    by_class: dict = collections.defaultdict(float)
    outer: dict = collections.defaultdict(float)
    inner: dict = collections.defaultdict(float)
    unscoped: dict = collections.defaultdict(float)
    for op in trace_reduce.first_device(trace):
        op_name, cls = names.get(op.name, ("", "unscoped"))
        seconds = op.end - op.start
        scopes = scope_of(op_name)
        by_class[cls] += seconds
        outer[(cls, "/".join(scopes[:2]))] += seconds
        # The innermost scope without its number: every Conv, every attn.
        inner[(cls, re.sub(r"_?\d+$", "", scopes[-1]) if scopes else "")] \
            += seconds
        if cls == "unscoped":
            unscoped[(trace_reduce.group_name(op.name), op_name)] += seconds

    def table(title: str, rows: dict) -> list:
        return [title] + [f"  {s:12.6f}  {c:14s} {scope}" for (c, scope), s
                          in sorted(rows.items(), key=lambda kv: -kv[1])[:top]]

    return "\n".join(
        ["chip 0, whole file, seconds by class:"]
        + [f"  {by_class[c]:12.6f}  {c}" for c in CLASSES]
        + table("by class and outer scopes:", outer)
        + table("by class and innermost scope:", inner)
        + ["largest unscoped operations (name, op_name):"]
        + [f"  {s:12.6f}  {name}  {op_name!r}" for (name, op_name), s in
           sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]])


if __name__ == "__main__":
    print(describe(sys.argv[1]))
