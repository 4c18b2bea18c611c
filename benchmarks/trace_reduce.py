"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The device planes hold one event for each XLA operation that ran, in ns
since the profile started; the ``Task Environment`` plane says when that
was on the host's wall clock. The benchmark's loop times its three calls a
step (``place``, ``dispatch``, ``fence``) on that same clock, so its spans go
onto the trace's clock by a subtraction and need nothing from the
profiler's host tracer. Everything here is interval arithmetic on those
two, in seconds since the profile started. ``python
benchmarks/trace_reduce.py <file.xplane.pb>`` prints what a trace holds, for
the look by hand that has to come before any change to the matching below.

What the trace of this program looks like on a v5e is written in PERF.md
("Reading the trace").
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OPS_LINE = "XLA Ops"
ENVIRONMENT_PLANE = "Task Environment"
PROFILE_START = "profile_start_time"        # ns since the epoch
# XLA numbers its instructions: "fusion.412", "all-reduce.3". The breakdown
# groups by the name without the number.
_NUMBER = re.compile(r"\.\d+$")
# On the v5e a device event's name is the whole HLO instruction as XLA
# prints it ("%all-reduce.1 = f32[...] all-reduce(...), replica_groups=...").
# ``Op.name`` is the instruction's name; ``Op.tags`` the rest, to match on.
_INSTRUCTION = re.compile(r"^%?(\S+) = (.*)$", re.S)


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float
    tags: str = ""


@dataclasses.dataclass
class Trace:
    devices: dict        # plane name -> [Op], sorted by start
    spans: dict          # the loop's span name -> [(start, end)], sorted


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: str, spans_ns: dict) -> Trace:
    """``spans_ns``: the loop's spans, name -> [(start, end)] in ns since the
    epoch (``time.time_ns``)."""
    from jax.profiler import ProfileData

    devices: dict = {}
    start_ns = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == ENVIRONMENT_PLANE:
            start_ns = dict(plane.stats)[PROFILE_START]
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != DEVICE_OPS_LINE:
                continue
            for ev in line.events:
                m = _INSTRUCTION.match(ev.name)
                name, tags = m.groups() if m else (ev.name, "")
                ops.append(Op(name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9, tags))
        devices[plane.name] = sorted(ops, key=lambda o: o.start)
    if start_ns is None:
        raise ValueError(f"{path} does not say when the profile started")
    return Trace(devices, {
        name: sorted(((a - start_ns) * 1e-9, (b - start_ns) * 1e-9)
                     for a, b in spans)
        for name, spans in spans_ns.items()})


# ---- interval arithmetic -------------------------------------------------

def merge(intervals) -> list:
    """Union of intervals as a sorted list of disjoint ones."""
    out: list = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b) -> list:
    """The parts of ``a`` that no interval of ``b`` covers (both merged)."""
    out = []
    b = list(b)
    for lo, hi in a:
        cur = lo
        for blo, bhi in b:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of a merged busy list inside ``[lo, hi]``."""
    return subtract([(lo, hi)], busy)


# ---- what the metrics read -----------------------------------------------

def window_of(trace: Trace) -> tuple:
    """The traced window: first ``place`` or ``dispatch`` start to last
    ``fence`` end. The loop fences just before it starts tracing, so a
    window is a whole number of the loop's own periods."""
    starts = [s for name in ("place", "dispatch")
              for s, _ in trace.spans.get(name, [])]
    ends = [e for _, e in trace.spans.get("fence", [])]
    if not starts or not ends:
        raise ValueError("no place or dispatch .. fence spans to set a "
                         "window by")
    return min(starts), max(ends)


def busy_of(ops, lo: float, hi: float) -> list:
    return clip(merge((o.start, o.end) for o in ops), lo, hi)


def busy_seconds(trace: Trace) -> tuple:
    """(busy seconds averaged over the device planes, window seconds)."""
    lo, hi = window_of(trace)
    per_device = [total(busy_of(ops, lo, hi))
                  for ops in trace.devices.values()]
    if not per_device:
        raise ValueError("trace holds no device plane")
    return sum(per_device) / len(per_device), hi - lo


def idle_by_segment(trace: Trace) -> list:
    """Chip 0's idle share in each of the loop's segments (one ends with
    each ``fence``): a traced stretch that is steady shows the same in
    all."""
    lo, _ = window_of(trace)
    ops, out = first_device(trace), []
    for _, hi in trace.spans["fence"]:
        out.append(1 - total(busy_of(ops, lo, hi)) / (hi - lo))
        lo = hi
    return out


def matching(ops, pattern: str) -> list:
    """Operations whose name or tags match ``pattern`` (a regex)."""
    rx = re.compile(pattern)
    return [o for o in ops if rx.search(o.name) or rx.search(o.tags)]


def first_device(trace: Trace) -> list:
    """Chip 0's operations: per-step sums are read on one chip, since every
    chip runs the same program."""
    if not trace.devices:
        return []
    key = min(trace.devices, key=lambda n: int(DEVICE_PLANE.match(n).group(1)))
    return trace.devices[key]


def op_seconds(trace: Trace, pattern: str) -> float:
    """Summed durations, inside the window on chip 0, of matching ops."""
    lo, hi = window_of(trace)
    return total(clip([(o.start, o.end)
                       for o in matching(first_device(trace), pattern)],
                      lo, hi))


def exposed_seconds(trace: Trace, pattern: str) -> float:
    """Seconds, inside the window on chip 0, in which a matching operation
    ran and no other operation did."""
    lo, hi = window_of(trace)
    ops = first_device(trace)
    hit = set(matching(ops, pattern))
    mine = clip(merge((o.start, o.end) for o in hit), lo, hi)
    others = merge((o.start, o.end) for o in ops if o not in hit)
    return total(subtract(mine, others))


def group_name(name: str) -> str:
    return _NUMBER.sub("", name)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time on chip 0 inside the
    window, grouped by XLA's name without its number, and the idle time of
    chip 0 by what the host did in each gap: the first of the loop's spans
    to start inside it (what the chip then waited for) or, where none did,
    the span open at its middle."""
    lo, hi = window_of(trace)
    ops = first_device(trace)
    by_name: dict = collections.defaultdict(float)
    for o in ops:
        for a, b in clip([(o.start, o.end)], lo, hi):
            by_name[group_name(o.name)] += b - a
    host = sorted((s, e, name)
                  for name, ivs in trace.spans.items() for s, e in ivs)
    by_span: dict = collections.defaultdict(float)
    for a, b in gaps(busy_of(ops, lo, hi), lo, hi):
        mid = (a + b) / 2
        owner = next((n for s, _, n in host if a <= s < b), None) \
            or next((n for s, e, n in host if s <= mid < e), "between_spans")
        by_span[owner] += b - a

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(by_name), "idle_gaps": ranked(by_span)}


def describe(path: str, top: int = 25) -> str:
    """What a trace file holds: planes, lines, the commonest names and the
    statistics they carry. For reading by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            secs: dict = collections.defaultdict(float)
            sample: dict = {}
            for ev in events:
                m = _INSTRUCTION.match(ev.name)
                key = group_name(m.group(1) if m else ev.name)
                secs[key] += ev.duration_ns * 1e-9
                sample.setdefault(key, ev)
            for key, s in sorted(secs.items(), key=lambda kv: -kv[1])[:top]:
                ev = sample[key]
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in dict(ev.stats).items()}
                out.append(f"    {s:10.6f} s  {key[:60]!r}  e.g. "
                           f"{ev.name[:300]!r} start_ns={ev.start_ns:.0f} "
                           f"stats={stats}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
