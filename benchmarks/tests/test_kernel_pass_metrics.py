"""The metrics of ISSUE 51, which read the program's placement of its own
Mosaic kernels (``hvd.compiled_step_report``'s ``kernel_calls``): the expert
layer's grouped matmuls by pass, on a trace written out below against a stub
of the report, and the count of kernels the report places nowhere. And the
report's reading of an ``op_name`` against this benchmark's own
(``scope_reduce.classify``, ``scope_of``) on every name in the compiled text
the program's tests keep (``tests/data/hlo``)."""

import glob
import importlib
import json
import os
import re
import types

import pytest

from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = {"moe_experts_fwd_ms": "Expert layer",
       "moe_experts_remat_ms": "Expert layer",
       "moe_experts_bwd_ms": "Expert layer",
       "tok_kernels_unplaced": "Compiled step"}
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 40 * MS)]}
STEPS = 2
# Chip 0, in the order it ran: (instruction, ms). The window is [10, 40] ms
# and the first event starts at 12, so the last one's final 2 ms lie outside.
EVENTS = [("ragged-dot-metadata.2", 1), ("ragged-dot-none.9", 2),
          ("fusion.1", 1), ("hvd_flash_fwd.1", 5), ("ragged-dot-none.4", 3),
          ("ragged-dot-none.36", 4),
          # A loop's body: one instruction, an event a turn.
          ("ragged-dot-none.1", 2), ("fusion.2", 1), ("ragged-dot-none.1", 2),
          ("ragged-dot-none.5", 9)]
CALLS = [
    {"instruction": name, "kernel": re.sub(r"\.\d+$", "", name),
     "pass": which, "placed_by": by, "loop": loop, "op_name": "", "scope": ""}
    for name, which, by, loop in [
        ("ragged-dot-metadata.2", "forward", "operands", False),
        ("ragged-dot-none.9", "forward", "operands", False),
        ("hvd_flash_fwd.1", "forward", "own", False),
        ("ragged-dot-none.4", "recomputation", "operands", False),
        ("ragged-dot-none.36", "backward", "operands", False),
        ("ragged-dot-none.1", "backward", "own", True),
        ("ragged-dot-none.5", "backward", "operands", False),
        # In the program and not in these two steps' trace.
        ("ragged-dot-none.8", "forward", "operands", False)]]
WANT = {"moe_experts_fwd_ms": (1 + 2) / STEPS,
        "moe_experts_remat_ms": 3 / STEPS,
        "moe_experts_bwd_ms": (4 + 2 + 2 + 7) / STEPS}


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData

    keys, events, at = {}, [], 12
    for name, ms in EVENTS:
        key = keys.setdefault(name, len(keys) + 1)
        events.append(f"events {{ metadata_id: {key} offset_ps: "
                      f"{at * 10**9} duration_ps: {ms * 10**9} }}")
        at += ms
    metadata = " ".join(
        f'event_metadata {{ key: {key} value {{ id: {key} display_name: '
        f'"{name}" name: "%{name} = f32[8]{{0}} custom-call()" }} }}'
        for name, key in keys.items())
    path = tmp_path_factory.mktemp("passes") / "passes.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(f'''
planes {{ id: 1 name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: {START_NS} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" {" ".join(events)} }} {metadata} }}'''))
    return tr.read_xplane(str(path), SPANS_NS)


def ctx_of(trace=None, grouped=True):
    costs = {"grouped_matmul": {"match": "^ragged-dot-", "ops": 1e9,
                                "bytes": 1e6}} if grouped else {}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs, step=object()), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=STEPS)


@pytest.fixture
def reported(monkeypatch):
    """``hvd.compiled_step_report`` stood in for, answering with ``made``."""
    import horovod_tpu as hvd

    made = {"seconds": 0.5, "kernels": {}, "kernel_calls": CALLS}
    monkeypatch.setattr(hvd, "compiled_step_report", lambda step: made,
                        raising=False)
    return made


@pytest.mark.parametrize("metric", sorted(NEW))
def test_every_new_metric_has_an_entry_and_a_reader(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["layer"] == NEW[metric]
    reports = {c for m in bench["end_to_end"] if m["name"] == entry["moves"]
               for c in m["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= reports
    assert callable(reader(metric))


@pytest.mark.parametrize("metric", sorted(WANT))
def test_grouped_kernels_by_pass(metric, trace, reported):
    assert reader(metric)(ctx_of(trace)) == pytest.approx(WANT[metric])


def test_the_passes_add_up_to_the_kernels_time(trace, reported):
    ctx = ctx_of(trace)
    assert sum(reader(m)(ctx) for m in WANT) \
        == pytest.approx(ctx.kernel_ms_per_step("grouped_matmul")) \
        == pytest.approx(21 / STEPS)


def test_unplaced_kernels_are_counted(reported):
    assert reader("tok_kernels_unplaced")(ctx_of()) == 0.0
    reported["kernel_calls"] = CALLS + [dict(CALLS[0], **{"pass": "none"})]
    assert reader("tok_kernels_unplaced")(ctx_of()) == 1.0


@pytest.mark.parametrize("metric", sorted(NEW))
def test_nothing_to_read_gives_nothing(metric, trace, reported, monkeypatch):
    """The parent commit under this benchmark (a report without
    ``kernel_calls``, or no report at all), a dense job, a rehearsal's trace
    (no device plane): None, never an error."""
    import horovod_tpu as hvd

    if metric != "tok_kernels_unplaced":
        assert reader(metric)(ctx_of(trace, grouped=False)) is None
        assert reader(metric)(ctx_of(tr.Trace({}, {}))) is None
        assert reader(metric)(ctx_of(None)) is None
    del reported["kernel_calls"]
    assert reader(metric)(ctx_of(trace)) is None
    monkeypatch.delattr(hvd, "compiled_step_report")
    assert reader(metric)(ctx_of(trace)) is None


# ---- the program's reading of a name against the benchmark's own -------------

def fixture_op_names():
    names = set()
    for path in glob.glob(os.path.join(ROOT, "tests", "data", "hlo",
                                       "*.hlo.txt")):
        with open(path) as f:
            names.update(re.findall(r'op_name="([^"]*)"', f.read()))
    return sorted(names)


def test_the_report_and_the_trace_readers_read_names_alike():
    from horovod_tpu import hlo_report

    names = fixture_op_names()
    again = [n for n in names if hlo_report.pass_of(n) == "recomputation"
             and "rematted_computation" not in n]
    # A windowed layer's backward rule making a window's forward again: made
    # again to the report, ``backward`` to the readers of a trace.
    assert again and all("/jvp(jit(_window))/" in n for n in again)
    assert {sr.classify(n) for n in again} == {"backward"}
    seen = set()
    for name in names:
        assert hlo_report.scope_of(name) == "/".join(sr.scope_of(name)), name
        if name not in again:
            want = sr.classify(name)
            seen.add(want)
            assert hlo_report.pass_of(name) \
                == {"unscoped": "none"}.get(want, want), name
    assert seen == {"forward", "recomputation", "backward", "unscoped"}
