"""What the compiler made of a program, read from the executable itself.

``hvd.compiled_step_report`` (``step.py``) reduces the step a job runs to
this; ``scripts/aot_step.py`` the same step compiled in the sandbox for a chip
that is described and not attached, through the same function, so both count
alike. Standard library only: the script loads this file by its path beside
another checkout's package.
"""

from __future__ import annotations

import collections
import io
import math
import re

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# "  [ROOT ]%name = type opcode(operands), attributes": a tuple's type holds
# spaces, so the opcode is the last word before the first "(" that follows a
# space.
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.-]+) = (\(.*?\) |\S+ )([\w-]+)\(%?([\w.-]*)")
# The names XLA's rematerialisation pass gives its clones ("remat2.5" is
# JAX's own primitive and no clone).
_REMAT = re.compile(r"\.remat(\d+|_\w+)?(\.\d+)?$")
_ARRAY = re.compile(r"\b(?:pred|[a-z]+(\d+)\w*)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _result_bytes(type_text: str) -> int:
    return sum(
        math.prod(int(d) for d in dims.split(",") if d) * int(bits or 8) // 8
        for bits, dims in _ARRAY.findall(type_text))


def reduce_hlo(lines) -> dict:
    """An optimized HLO module's text, a line at a time, to what the compiler
    put there beyond what the program asked for: instructions its
    rematerialisation pass made again (tuple reads apart), each with its
    result's bytes and the program's ``op_name``; copies of the entry
    computation's parameters (``copy``: a ``copy-start`` is a prefetch the
    step does not wait for); ``while`` loops; collectives by kind (a
    ``-start`` counts, its ``-done`` does not); Mosaic kernels by name."""
    out = {"instructions": 0, "rematerialized": [], "whiles": 0,
           "parameter_copies": {"count": 0, "bytes": 0},
           "collectives": collections.Counter(),
           "kernels": collections.Counter()}
    parameters = None       # the entry computation's, while inside it
    for line in lines:
        if line.startswith("ENTRY "):
            parameters = set()
        elif line.startswith("}"):
            parameters = None
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, type_text, opcode, operand = m.groups()
        out["instructions"] += 1
        if _REMAT.search(name) and opcode != "get-tuple-element":
            op_name = _OP_NAME.search(line)
            out["rematerialized"].append({
                "name": name, "opcode": opcode,
                "bytes": _result_bytes(type_text),
                "op_name": op_name.group(1) if op_name else ""})
        if opcode == "parameter" and parameters is not None:
            parameters.add(name)
        elif opcode == "copy" and operand in (parameters or ()):
            out["parameter_copies"]["count"] += 1
            out["parameter_copies"]["bytes"] += _result_bytes(type_text)
        elif opcode == "while":
            out["whiles"] += 1
        elif (kind := opcode.removesuffix("-start")) in _COLLECTIVES:
            out["collectives"][kind] += 1
        elif opcode == "custom-call" and '"tpu_custom_call"' in line:
            out["kernels"][re.sub(r"\.\d+$", "", name)] += 1
    return out


def compiled_report(compiled) -> dict:
    """:func:`reduce_hlo` of a ``jax.stages.Compiled``, and what its
    ``memory_analysis()`` says, in bytes."""
    m = compiled.memory_analysis()
    report = reduce_hlo(io.StringIO(compiled.as_text()))
    report["memory_bytes"] = {
        "arguments": m.argument_size_in_bytes,
        "outputs": m.output_size_in_bytes,
        "aliased": m.alias_size_in_bytes,
        "temporaries": m.temp_size_in_bytes,
        "generated_code": m.generated_code_size_in_bytes}
    return report
