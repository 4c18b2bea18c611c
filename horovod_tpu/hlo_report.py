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
from typing import NamedTuple, Optional

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# "  [ROOT ]%name = type opcode(operands), attributes": a tuple's type holds
# spaces, so the opcode is the last word before the first "(" that follows a
# space.
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%?([\w.-]+) = (\(.*?\) |\S+ )([\w-]+)\(%?([\w.-]*)")
# The names XLA's rematerialisation pass gives its clones ("remat2.5" is
# JAX's own primitive and no clone).
_REMAT = re.compile(r"\.remat(\d+|_\w+)?(\.\d+)?$")
_ARRAY = re.compile(r"\b(?:pred|[a-z]+(\d+)\w*)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NUMBER = re.compile(r"\.\d+$")
_OPERAND = re.compile(r"%([\w.-]+)")
_TUPLE_INDEX = re.compile(r"\bindex=(\d+)")
# The computations an instruction runs: a fusion's, a call's, a loop's body.
_CALLED = re.compile(r"\b(body|calls|to_apply)=%([\w.-]+)")
_BRANCHES = re.compile(r"\b(?:branch|called)_computations=\{([^}]*)\}")
# JAX's name for the backward pass of a checkpointed block X; what follows
# it is the block's own scopes.
_CHECKPOINT_BACKWARD = re.compile(
    r"transpose\(jvp\(([^/]+)\)\)/jvp\(\1\)/checkpoint/")
_WRAPPER = re.compile(r"^(?:transpose|jvp|jit|pjit)\((.*)\)$")
_NO_SCOPE = ("shard_map", "checkpoint", "remat2", "rematted_computation")
# How far a kernel's operand is followed to the instruction that computes it:
# XLA puts a handful of copies and tuple reads between the two.
_HOPS = 32


def _result_bytes(type_text: str) -> int:
    return sum(
        math.prod(int(d) for d in dims.split(",") if d) * int(bits or 8) // 8
        for bits, dims in _ARRAY.findall(type_text))


def pass_of(op_name: str) -> str:
    """The pass of a training step an ``op_name`` lies in, by the markers JAX
    puts there: ``forward`` (``jvp(``), ``backward`` (``transpose(jvp(``),
    ``recomputation`` (a checkpointed block made again,
    ``rematted_computation``; and, in a checkpointed block's backward pass, a
    ``jvp(`` that no ``transpose(`` wraps: a backward rule making its own
    forward again with ``jax.vjp``), ``none`` where it holds no marker."""
    if "rematted_computation" in op_name:
        return "recomputation"
    block = _CHECKPOINT_BACKWARD.search(op_name)
    if block:
        inner = op_name[block.end():].split("/")
        return "recomputation" if any(
            part.startswith("jvp(") for part in inner) else "backward"
    if "transpose(jvp(" in op_name:
        return "backward"
    return "forward" if "jvp(" in op_name else "none"


def scope_of(op_name: str) -> str:
    """The program's scopes in an ``op_name``, outermost first, without the
    jitted step, JAX's wrappers and the primitive: ``jit(step)/transpose(jvp(
    layer0))/jvp(layer0)/checkpoint/rematted_computation/moe/dispatch/gather``
    is ``layer0/moe/dispatch``."""
    parts: list = []
    for part in op_name.split("/")[1:-1]:
        while (inner := _WRAPPER.match(part)):
            part = inner.group(1)
        if part and part not in _NO_SCOPE and part not in parts[-1:]:
            parts.append(part)
    return "/".join(parts)


def _operands(line: str, start: int) -> list:
    """The operands' names of an instruction's line, from ``start``, just
    after its opcode's bracket, to the bracket that closes it."""
    depth, i = 1, start
    while depth and i < len(line):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        i += 1
    return _OPERAND.findall(line, start, i)


def _rank(type_text: str) -> int:
    """An array type's rank; -1 for a tuple or a token."""
    arrays = _ARRAY.findall(type_text)
    if type_text.startswith("(") or not arrays:
        return -1
    return len([d for d in arrays[0][1].split(",") if d])


class _Instruction(NamedTuple):
    type: str
    opcode: str
    operands: list      # their names
    op_name: str
    line: str


class _Module:
    """The lines of a module by the names they define, for the few
    instructions a placement looks up: a line is parsed when asked for."""

    def __init__(self):
        self.lines: dict = {}       # instruction -> its line
        self.roots: dict = {}       # computation -> its root instruction
        self.callers: dict = collections.defaultdict(set)
        self.bodies: set = set()    # computations that are a while's body

    def parsed(self, name: str) -> Optional[_Instruction]:
        line = self.lines.get(name)
        m = _INSTRUCTION.match(line) if line else None
        if not m:
            return None
        op_name = _OP_NAME.search(line, m.end())
        return _Instruction(
            m.group(3), m.group(4), _operands(line, m.end(4) + 1),
            op_name.group(1) if op_name else "", line)

    def in_loop(self, computation: str) -> bool:
        return computation in self.bodies or any(
            map(self.in_loop, self.callers.get(computation, ())))

    def fused_output(self, read: _Instruction) -> Optional[str]:
        """For a read of a fusion with several outputs, the instruction of
        the fused computation that makes the output read (the root tuple's
        operand at the read's index); None for anything else."""
        if read.opcode != "get-tuple-element" or not read.operands:
            return None
        fusion = self.parsed(read.operands[0])
        called = _CALLED.search(fusion.line) if fusion \
            and fusion.opcode == "fusion" else None
        root = self.parsed(self.roots.get(called.group(2), "")) \
            if called else None
        index = _TUPLE_INDEX.search(read.line)
        if root and root.opcode == "tuple" and index \
                and int(index.group(1)) < len(root.operands):
            return root.operands[int(index.group(1))]
        return None

    def maker(self, name: str) -> Optional[str]:
        """The ``op_name`` of the instruction that computes the value
        ``name`` holds, where it has one of the program's pass markers:
        through instructions that have none (XLA's own ``copy``, ``bitcast``,
        ``copy-done``) by their first operand, and through a read of a fusion
        with several outputs into the fused computation (the fusion and every
        read of it carry one name, its root's). None where that leads to no
        such name."""
        for _ in range(_HOPS):
            ins = self.parsed(name)
            if ins is None:
                return None
            inside = self.fused_output(ins)
            if inside:
                name = inside
            elif pass_of(ins.op_name) != "none":
                return ins.op_name
            elif ins.operands:
                name = ins.operands[0]
            else:
                return None
        return None

    def place(self, name: str, computation: str) -> dict:
        """One ``tpu_custom_call`` in a pass and a scope: by its own
        ``op_name`` where that holds a pass marker, else by the maker of its
        first array operand of rank 2 or more (of its first operand, if it
        has none such: a kernel over group sizes)."""
        kernel = self.parsed(name)
        call = {"instruction": name, "kernel": _NUMBER.sub("", name),
                "op_name": kernel.op_name, "pass": pass_of(kernel.op_name),
                "scope": scope_of(kernel.op_name), "placed_by": "own",
                "loop": self.in_loop(computation)}
        if call["pass"] != "none":
            return call
        call["placed_by"] = "operands"
        if not kernel.operands:
            return call
        ranks = [_rank(ins.type) if ins else -1
                 for ins in map(self.parsed, kernel.operands)]
        rows = next((i for i, r in enumerate(ranks) if r >= 2), 0)
        found = self.maker(kernel.operands[rows])
        if found:
            call.update(op_name=found, scope=scope_of(found))
            # A result of higher rank than the rows it was made from is a
            # gradient of stacked matrices (a grouped matmul's weight
            # gradient): backward whatever made its rows.
            call["pass"] = "backward" \
                if 2 <= ranks[rows] < _rank(kernel.type) else pass_of(found)
        return call


def reduce_hlo(lines) -> dict:
    """An optimized HLO module's text, a line at a time, to what the compiler
    put there beyond what the program asked for: instructions its
    rematerialisation pass made again (tuple reads apart), each with its
    result's bytes and the program's ``op_name``; copies of the entry
    computation's parameters (``copy``: a ``copy-start`` is a prefetch the
    step does not wait for); ``while`` loops; collectives by kind (a
    ``-start`` counts, its ``-done`` does not); Mosaic kernels by name
    (``kernels``) and each of them placed (``kernel_calls``, in the text's
    order): ``instruction`` (what a device event is called), ``kernel`` (the
    same without XLA's number), ``pass`` (:func:`pass_of`), ``scope``
    (:func:`scope_of`), the ``op_name`` both were read from, ``placed_by``
    (``own``: that name is the kernel's own; ``operands``: its own holds no
    pass marker, as XLA's name for a grouped matmul holds none, and the name
    is that of the instruction that makes its rows) and ``loop`` (the kernel
    is in a ``while``'s body, directly or through calls: a step runs it as
    often as the loop turns)."""
    out = {"instructions": 0, "rematerialized": [], "whiles": 0,
           "parameter_copies": {"count": 0, "bytes": 0},
           "collectives": collections.Counter()}
    module, kernels = _Module(), []
    computation = None
    parameters = None       # the entry computation's, while inside it
    for line in lines:
        if line.startswith("ENTRY "):
            parameters = set()
            computation = line.split(" ", 2)[1].lstrip("%")
        elif line.startswith("%"):
            computation = line[1:line.index(" ")]
        elif line.startswith("}"):
            parameters = None
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, name, type_text, opcode, operand = m.groups()
        out["instructions"] += 1
        module.lines[name] = line
        if root:
            module.roots[computation] = name
        if "=%" in line:
            for kind, called in _CALLED.findall(line, m.end()):
                module.callers[called].add(computation)
                if kind == "body":
                    module.bodies.add(called)
            for group in _BRANCHES.findall(line, m.end()):
                for called in _OPERAND.findall(group):
                    module.callers[called].add(computation)
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
            kernels.append((name, computation))
    out["kernel_calls"] = [module.place(*kernel) for kernel in kernels]
    out["kernels"] = collections.Counter(
        call["kernel"] for call in out["kernel_calls"])
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
