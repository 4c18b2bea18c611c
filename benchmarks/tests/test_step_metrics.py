"""The metrics of ISSUE 33: three that read the program's report of its own
compiled step (``hvd.compiled_step_report``) on a stub of it, and
``moe_windows_per_step`` on a trace written out below: two steps of a program
whose layer 0 takes one window a step and whose layer 1 takes three."""

import importlib
import json
import os
import types

import pytest

from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext
from benchmarks.layer_metrics import moe_windows_per_step

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = {"tok_compiler_remat": "Compiled step",
       "tok_step_memory_gib": "Compiled step",
       "collective_count": "Gradient exchange",
       "moe_windows_per_step": "Expert layer"}
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 200 * MS)]}
STEPS = 2
GIB = 2 ** 30


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def ctx_of(trace=None, **job):
    return RunContext(
        job=types.SimpleNamespace(kernel_costs={}, **job), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=STEPS)


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


# ---- the three that read the program's report --------------------------------

REPORT = {
    "instructions": 1000, "whiles": 2, "seconds": 0.5,
    "rematerialized": [
        {"name": "fusion.11.remat", "opcode": "fusion", "bytes": 64,
         "op_name": "jit(step)/jvp(head)/dot_general"},
        {"name": "convert.3.remat2", "opcode": "convert", "bytes": 8,
         "op_name": ""}],
    "parameter_copies": {"count": 3, "bytes": 96},
    "collectives": {"all-reduce": 5, "all-gather": 1},
    "kernels": {"hvd_flash_fwd": 6},
    "memory_bytes": {"arguments": 8 * GIB, "outputs": 7 * GIB,
                     "aliased": 7 * GIB, "temporaries": 6 * GIB,
                     "generated_code": 0}}


@pytest.fixture
def reported(monkeypatch):
    """``hvd.compiled_step_report`` stood in for: it answers for the job's
    step alone, and says how often it was asked."""
    import horovod_tpu as hvd

    step, asked = object(), []

    def report(of):
        assert of is step
        asked.append(of)
        return REPORT

    monkeypatch.setattr(hvd, "compiled_step_report", report, raising=False)
    return ctx_of(step=step), asked


@pytest.mark.parametrize("metric, want", [
    ("tok_compiler_remat", 2.0),
    # arguments + temporaries + outputs - aliased
    ("tok_step_memory_gib", 14.0),
    ("collective_count", 6.0)])
def test_report_readers(metric, want, reported):
    ctx, asked = reported
    assert reader(metric)(ctx) == pytest.approx(want)
    assert len(asked) == 1


@pytest.mark.parametrize("metric", ["tok_compiler_remat",
                                    "tok_step_memory_gib",
                                    "collective_count"])
def test_a_program_without_the_report_gives_nothing(metric, monkeypatch):
    """The parent commit under this benchmark: no such call, no value and no
    error."""
    import horovod_tpu as hvd

    monkeypatch.delattr(hvd, "compiled_step_report", raising=False)
    assert reader(metric)(ctx_of(step=object())) is None


# ---- the windows, from a trace ------------------------------------------------

FWD = "jit(step)/shard_map/jvp(layer{i})/moe/windows/"
BWD = "jit(step)/shard_map/transpose(jvp(layer{i}))/jvp(layer{i})/checkpoint/"
FIRST = "window/jit(_window)/"
BODY = "while/body/window/jit(_window)/"


def layer_events(i: int, turns: int) -> list:
    """``(instruction, op_name)`` in the order a chip runs one layer's forward
    windows, as a v5e's trace of the layer alone shows them (my chip run, PR
    33): the window at 0; a reshape the compiler lifted out of the loop's
    body, once a step whether the body runs or not; the loop, whose own event
    spans its turns; then what the backward rule, a recomputed copy and the
    rest of the layer run, none of it counted."""
    fwd, bwd = FWD.format(i=i), BWD.format(i=i)
    body = [(f"fusion.{i}21", fwd + BODY + "dispatch/gather"),
            (f"ragged-dot-none.{i}3", "ragged-dot-none"),
            (f"fusion.{i}22", fwd + BODY + "combine/scatter-add"),
            (f"add.{i}23", fwd + "while/body/add")]
    return [(f"fusion.{i}10", fwd + FIRST + "dispatch/gather"),
            (f"ragged-dot-none.{i}1", fwd + FIRST + "ragged-dot-none"),
            (f"fusion.{i}11", fwd + FIRST + "combine/scatter-add"),
            (f"reshape.{i}12", fwd + BODY + "combine/reshape"),
            (f"while.{i}", fwd + "while", body * turns),
            (f"fusion.{i}30", bwd + "moe/windows/" + BODY.replace(
                "jit(_window)", "jvp(jit(_window))") + "dispatch/gather"),
            (f"fusion.{i}31", bwd + "rematted_computation/moe/windows/"
             + FIRST + "dispatch/gather"),
            (f"fusion.{i}40",
             f"jit(step)/shard_map/jvp(layer{i})/moe/router/dot_general")]


def textproto(steps: int, turns: dict) -> str:
    """An XSpace with one device plane: ``steps`` steps of layers that take
    ``turns[layer]`` turns of their loops, a millisecond an event from 12 ms
    on, a loop's event over its body's."""
    keys, events, at = {}, [], 12

    def event(name, op_name, start, length):
        keys.setdefault(name, (len(keys) + 1, op_name))
        events.append(f"events {{ metadata_id: {keys[name][0]} offset_ps: "
                      f"{start * 10**9} duration_ps: {length * 10**9} }}")

    for _ in range(steps):
        for layer, n in turns.items():
            for name, op_name, *inside in layer_events(layer, n):
                inside = inside[0] if inside else []
                event(name, op_name, at, len(inside) + 1)
                for k, (inner, inner_op_name) in enumerate(inside, 1):
                    event(inner, inner_op_name, at + k, 1)
                at += len(inside) + 1
    metadata = " ".join(
        f'event_metadata {{ key: {key} value {{ id: {key} display_name: '
        f'"{name}" name: "%{name} = f32[8]{{0}} fusion()" '
        f'stats {{ metadata_id: 2 str_value: "{op_name}:" }} }} }}'
        for name, (key, op_name) in keys.items())
    return f'''
planes {{ id: 1 name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: {START_NS} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" {" ".join(events)} }}
  {metadata}
  stat_metadata {{ key: 2 value {{ id: 2 name: "tf_op" }} }} }}'''


@pytest.fixture(scope="module")
def windows_trace(tmp_path_factory):
    """Layer 0 takes one window a step (its loop's body never runs), layer 1
    three (two turns)."""
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("windows") / "windows.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        textproto(STEPS, {0: 0, 1: 2})))
    return str(path)


def test_windows_are_counted_by_layer_and_loop(windows_trace):
    trace = tr.read_xplane(windows_trace, SPANS_NS)
    taken = moe_windows_per_step.windows_taken(
        tr.first_device(trace), sr.program_names(windows_trace))
    # Nothing for layer 0's loop: what was lifted out of its body ran, the
    # body did not.
    assert taken == {(("layer0", "moe"), False): STEPS,
                     (("layer1", "moe"), False): STEPS,
                     (("layer1", "moe"), True): 2 * STEPS}


def test_windows_per_step_sums_the_layers(windows_trace, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: windows_trace)
    ctx = ctx_of(tr.read_xplane(windows_trace, SPANS_NS))
    assert reader("moe_windows_per_step")(ctx) == pytest.approx(1 + 3)


def test_an_instruction_the_window_cuts_does_not_move_the_count(
        windows_trace):
    """The count is the one most instructions of a group share: one whose
    event the traced window cut off is outvoted."""
    ops = tr.first_device(tr.read_xplane(windows_trace, SPANS_NS))
    cut = next(op for op in ops if op.name == "fusion.122")
    taken = moe_windows_per_step.windows_taken(
        [op for op in ops if op is not cut], sr.program_names(windows_trace))
    assert taken[(("layer1", "moe"), True)] == 2 * STEPS


@pytest.mark.parametrize("what", ["a dense program", "no device plane"])
def test_no_window_scope_reads_nothing(what, monkeypatch):
    dense = os.path.join(HERE, "data", "scoped_trace.xplane.pb")
    monkeypatch.setattr(sr, "newest_xplane", lambda: dense)
    trace = tr.read_xplane(dense, SPANS_NS) if what == "a dense program" \
        else tr.Trace({}, {})
    assert reader("moe_windows_per_step")(ctx_of(trace)) is None
