#!/usr/bin/env python3
"""The passes of their own that XLA put round the kernels of a compiled step:
every top-level ``copy`` of the entry computation of an HLO text that
``scripts/aot_step.py <cell> --hlo DIR`` wrote, by bytes and ``op_name``.

    python3 scripts/aot_step.py trinity-mini_s8192 --hlo /root/scratch/hlo
    python3 scripts/hlo_copies.py /root/scratch/hlo/trinity-mini_s8192.hlo.txt [--under attn] [--least-mb 5]
    python3 scripts/hlo_copies.py CHANGE.hlo.txt --cycles PARENT.hlo.txt

Bytes are a copy's output (it reads as much again); ``ms`` is read and write
at the v5e's 819 GB/s, which a ledger's ``copy`` row runs at about 0.83 of
(PERF.md, Findings, PR 70). A copy inside a fusion is no pass of its own and
is not counted. One JSON line a group of copies with the same shape, layout
and ``op_name`` less its layer; a last line of sums.

``--cycles PARENT`` reads no copies but the compiler's own
``"estimated_cycles"`` of every top-level instruction of both texts, summed by
``op_name`` less its layer: a first line of the two sums, then the rows that
differ most (ms at the v5e's 940 MHz). An estimate, not a time: for five
cells of five its sum had the sign of the chip's step, at 1.1 to 10 times its
size (PERF.md, Findings, PR 70), which is enough to rank two layouts before a
chip run. Nothing is compiled and nothing runs.
"""

from __future__ import annotations

import argparse
import collections
import json
import re

ITEMSIZE = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "f16": 2, "s8": 1,
            "u8": 1, "pred": 1, "s64": 8, "f64": 8, "u16": 2, "s16": 2}
HBM_BYTES_PER_S = 819e9
_COPY = re.compile(r"= (\w+)\[([\d,]*)\](\{[^ ]*\})? copy\(")
_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
_LAYER = re.compile(r"layer\d+|pass\d+")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
CLOCK_HZ = 940e6


def entry_lines(text: str):
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith("ENTRY "))
    for line in lines[start + 1:]:
        if line.startswith("}"):
            return
        yield line


def copies(text: str):
    """``(bytes, dtype[dims], the layout written, op_name)`` of every
    top-level copy."""
    for line in entry_lines(text):
        m = _COPY.search(line)
        if not m:
            continue
        dtype, dims, layout = m.groups()
        op_name = _OP_NAME.search(line)
        n = ITEMSIZE[dtype]
        for dim in filter(None, dims.split(",")):
            n *= int(dim)
        yield (n, f"{dtype}[{dims}]", layout or "",
               op_name.group(1) if op_name else "")


def cycles(text: str) -> collections.Counter:
    """The estimated cycles of the entry computation's instructions by
    ``op_name`` less its layer."""
    table = collections.Counter()
    for line in entry_lines(text):
        m = _CYCLES.search(line)
        if m:
            op_name = _OP_NAME.search(line)
            table[_LAYER.sub("*", op_name.group(1) if op_name else "")] \
                += int(m.group(1))
    return table


def compare_cycles(change: str, parent: str, rows: int = 30):
    with open(parent) as f:
        was = cycles(f.read())
    with open(change) as f:
        now = cycles(f.read())
    ms = lambda n: round(n / CLOCK_HZ * 1e3, 2)
    total = sum(was.values()), sum(now.values())
    print(json.dumps(dict(parent_ms=ms(total[0]), change_ms=ms(total[1]),
                          change_over_parent=round(total[1] / total[0], 4))))
    for name in sorted(set(was) | set(now),
                       key=lambda n: -abs(now[n] - was[n]))[:rows]:
        print(json.dumps(dict(parent_ms=ms(was[name]), change_ms=ms(now[name]),
                              op_name=name)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("hlo")
    ap.add_argument("--cycles", metavar="PARENT",
                    help="the compiler's estimated cycles by op_name, "
                         "this text against PARENT's")
    ap.add_argument("--under", default="",
                    help="only copies whose op_name holds this (attn)")
    ap.add_argument("--least-mb", type=float, default=0.0)
    args = ap.parse_args()
    if args.cycles:
        return compare_cycles(args.hlo, args.cycles)
    with open(args.hlo) as f:
        found = list(copies(f.read()))
    groups = collections.defaultdict(lambda: [0, 0])
    for n, shape, written, op_name in found:
        if args.under in op_name and n >= args.least_mb * 1e6:
            group = groups[shape, written, _LAYER.sub("*", op_name)]
            group[0] += 1
            group[1] += n
    ms = lambda n: round(2 * n / HBM_BYTES_PER_S * 1e3, 2)
    for (shape, written, op_name), (count, n) in sorted(
            groups.items(), key=lambda item: -item[1][1]):
        print(json.dumps(dict(copies=count, gb=round(n / 1e9, 3), ms=ms(n),
                              shape=shape, written=written,
                              op_name=op_name)))
    total = sum(n for n, *_ in found)
    shown = sum(n for _, n in groups.values())
    print(json.dumps(dict(
        all_copies=len(found), all_gb=round(total / 1e9, 3), all_ms=ms(total),
        shown=sum(c for c, _ in groups.values()),
        shown_gb=round(shown / 1e9, 3), shown_ms=ms(shown))))


if __name__ == "__main__":
    main()
