"""``scope_reduce.py`` against ``data/scoped_trace.xplane.pb``, whose
operations, ``op_name``s and expected sums are written out in
``data/scoped_trace.textproto`` (the ``.pb`` is that file through
``ProfileData.text_proto_to_serialized_xspace``)."""

import importlib
import os
import types

import pytest

from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PB = os.path.join(DATA, "scoped_trace.xplane.pb")
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
STEPS = 2
PREFIX = "jit(step)/shard_map/"


# ---- a protobuf writer as small as the reader, for the HLO plane -----------

def varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(name, opcode, op_name="", called=()):
    return field(2, field(1, name) + field(2, opcode)
                 + (field(7, field(2, op_name)) if op_name else b"")
                 + (field(38, b"".join(varint(c) for c in called))
                    if called else b""))


def hlo_plane() -> bytes:
    """An XSpace holding ``/host:metadata`` with the fixture's program:
    ``fusion.3`` fuses three instructions under ``hvd_optimizer``, a
    constant's broadcast and the ``add`` of ``apply_updates`` that is its
    root; ``fusion.1`` keeps its root's class whatever it fuses."""
    fused3 = field(1, "fused_computation.3") + field(5, 3) + b"".join([
        instruction("p", "parameter"),
        instruction("c", "broadcast", PREFIX + "broadcast.7"),
        instruction("mul.1", "multiply", PREFIX + "hvd_optimizer/mul"),
        instruction("add.1", "add", PREFIX + "hvd_optimizer/add"),
        instruction("div.1", "divide", PREFIX + "hvd_optimizer/div"),
        instruction("add.2", "add", PREFIX + "add")])
    fused1 = field(1, "fused_computation.1") + field(5, 1) + b"".join([
        instruction("r.1", "rsqrt", PREFIX + "transpose(jvp(head))/rsqrt"),
        instruction("r.2", "rsqrt", PREFIX + "transpose(jvp(head))/mul"),
        instruction("d", "dot", PREFIX + "jvp(layer0)/attn/dot_general")])
    main = field(1, "main") + field(5, 9) + b"".join([
        instruction("fusion.1", "fusion",
                    PREFIX + "jvp(layer0)/attn/dot_general", called=(1,)),
        instruction("fusion.3", "fusion", PREFIX + "add", called=(3,)),
        instruction("copy-done.1", "copy-done")])
    module = field(1, "jit_step") + field(3, fused3) + field(3, fused1) \
        + field(3, main)
    event_metadata = field(1, 1) + field(2, "jit_step(1)") + field(
        5, field(1, 1) + field(6, field(1, module)))
    plane = field(2, sr.HLO_PLANE) \
        + field(4, field(1, 1) + field(2, event_metadata)) \
        + field(5, field(1, 1) + field(2, field(1, 1)
                                       + field(2, sr.HLO_STAT)))
    return field(1, plane)


@pytest.fixture(scope="module")
def trace():
    return tr.read_xplane(PB, SPANS_NS)


@pytest.fixture()
def with_hlo(tmp_path):
    # Serialized messages concatenate: the planes of both are one XSpace.
    path = tmp_path / "with_hlo.xplane.pb"
    with open(PB, "rb") as f:
        path.write_bytes(f.read() + hlo_plane())
    return str(path)


def test_recorded_file_is_the_text_proto(trace, tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "scoped_trace.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "built.xplane.pb"
    path.write_bytes(built)
    assert tr.read_xplane(str(path), SPANS_NS) == trace
    assert sr.program_names(str(path)) == sr.program_names(PB)


@pytest.mark.parametrize("op_name, want", [
    (PREFIX + "hvd_exchange/hvd_allreduce/grads/mul", "exchange"),
    (PREFIX + "transpose(jvp(layer0))/jvp(layer0)/checkpoint/attn/"
     "psum_invariant", "exchange"),
    (PREFIX + "hvd_exchange/hvd_maxmin_quantize/pallas_call", "exchange"),
    (PREFIX + "hvd_optimizer/mul", "optimizer"),
    (PREFIX + "transpose(jvp(layer0))/jvp(layer0)/checkpoint/"
     "rematted_computation/attn/dot_general", "recomputation"),
    (PREFIX + "transpose(jvp(layer0))/jvp(layer0)/checkpoint/attn/"
     "dot_general", "backward"),
    (PREFIX + "jvp(layer0)/attn/dot_general", "forward"),
    ("jit(step)/jvp(ResNet)/stage0/BottleneckResNetBlock_0/Conv_0/"
     "conv_general_dilated", "forward"),
    (PREFIX + "hvd_allreduce/unnamed/psum", "unscoped"),
    (PREFIX + "jvp(loss)/psum_invariant", "forward"),
    (PREFIX + "add", "unscoped"),
    ("", "unscoped"),
])
def test_classify(op_name, want):
    assert sr.classify(op_name) == want


def test_names_from_the_device_events(trace):
    names = sr.program_names(PB)
    assert names["fusion.2"] == (
        PREFIX + "hvd_exchange/hvd_allreduce/grads/mul", "exchange")  # a ref
    assert names["fusion.5"][1] == "forward"    # no display name: from name
    assert "copy-done.1" not in names
    assert {op.name for op in tr.first_device(trace)} - set(names) \
        == {"copy-done.1"}


def test_class_sums_by_the_root(trace):
    got = sr.class_seconds(trace, sr.program_names(PB))
    assert got == pytest.approx({
        "forward": 0.009, "backward": 0.005, "recomputation": 0.003,
        "exchange": 0.003, "optimizer": 0.002, "unscoped": 0.007})
    lo, hi = tr.window_of(trace)
    assert sum(got.values()) == pytest.approx(
        tr.total(tr.busy_of(tr.first_device(trace), lo, hi)))


def test_a_fusion_without_a_class_goes_by_what_it_fuses(trace, with_hlo):
    names = sr.program_names(with_hlo)
    assert names["fusion.3"] == (PREFIX + "add", "optimizer")
    # A root with a class keeps it: two of three fused are backward.
    assert names["fusion.1"][1] == "forward"
    assert names["copy-done.1"] == ("", "unscoped")
    got = sr.class_seconds(trace, names)
    assert got["optimizer"] == pytest.approx(0.008)
    assert got["unscoped"] == pytest.approx(0.001)
    assert sum(got.values()) == pytest.approx(0.029)


def test_class_of_ties_and_constants():
    assert sr.class_of("", []) == "unscoped"
    assert sr.class_of("", ["x/broadcast.1"]) == "unscoped"
    assert sr.class_of("", ["a/jvp(f)/x", "a/transpose(jvp(f))/y"]) \
        == "backward"                              # the earlier in CLASSES
    assert sr.class_of("a/jvp(f)/x", ["a/hvd_optimizer/y"] * 3) == "forward"


def test_hlo_reader_on_a_module_xla_compiled():
    """The field numbers, against a proto XLA itself wrote."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("hvd_optimizer"):
            y = jnp.tanh(x) * 2.0 + 1.0
        return y.sum()

    compiled = jax.jit(f).lower(jnp.ones((64, 64))).compile()
    proto = compiled.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    names = sr.hlo_op_names(memoryview(proto))
    own = [op for op, _ in names.values()]
    fused = [n for _, inner in names.values() for n in inner]
    assert any("hvd_optimizer" in n for n in own + fused)
    text = compiled.as_text()
    assert all(name in text for name in names)
    if " fusion(" in text:
        assert fused


def fake_ctx(trace):
    job = types.SimpleNamespace(kernel_costs={}, flops_per_sample=1.0,
                                step=lambda: None)
    return RunContext(job=job, chips=2, peak={"bf16_flops_per_s": 1.0},
                      throughput=1.0, spans={}, first_step_s=1.0,
                      step_compiles=1, memory_peak_bytes=0, trace=trace,
                      steps_traced=STEPS)


# metric -> ms per traced step on the fixture, by the root and with the HLO
READERS = {
    "tok_fwd_ms": (4.5, 4.5), "img_fwd_ms": (4.5, 4.5),
    "tok_bwd_ms": (2.5, 2.5), "img_bwd_ms": (2.5, 2.5),
    "tok_remat_ms": (1.5, 1.5),
    "tok_optimizer_ms": (1.0, 4.0), "img_optimizer_ms": (1.0, 4.0),
    "grad_exchange_ms": (1.5, 1.5),
    "tok_unscoped_ms": (3.5, 0.5), "img_unscoped_ms": (3.5, 0.5),
    "flash_fwd_ms": (3.0, 3.0), "flash_dq_ms": (2.5, 2.5),
    "flash_dkdv_ms": (None, None),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader(metric, trace, with_hlo, monkeypatch):
    read = importlib.import_module(f"benchmarks.layer_metrics.{metric}").read
    for path, want in zip((PB, with_hlo), READERS[metric]):
        monkeypatch.setattr(sr, "newest_xplane", lambda p=path: p)
        got = read(fake_ctx(trace))
        assert got == (pytest.approx(want) if want is not None else None)


def test_readers_return_nothing_without_names_or_a_device(trace,
                                                          monkeypatch):
    read = importlib.import_module("benchmarks.layer_metrics.tok_fwd_ms").read
    # No device plane (a rehearsal on the CPU).
    assert read(fake_ctx(tr.Trace({}, trace.spans))) is None
    # A file that names none of the trace's operations.
    monkeypatch.setattr(sr, "newest_xplane", lambda: os.path.join(
        DATA, "tiny_trace.xplane.pb"))
    assert read(fake_ctx(trace)) is None
    monkeypatch.setattr(sr, "newest_xplane", lambda: None)
    assert read(fake_ctx(trace)) is None


def test_an_empty_class_reads_zero(trace, monkeypatch):
    """XLA fused the whole optimizer into backward kernels: a measurement."""
    monkeypatch.setattr(sr, "newest_xplane", lambda: PB)
    monkeypatch.setattr(sr, "program_names", lambda path: {
        op.name: ("x/jvp(f)/y", "forward") for op in tr.first_device(trace)})
    assert sr.class_ms(fake_ctx(trace), "optimizer") == 0.0
    assert sr.class_ms(fake_ctx(trace), "forward") == pytest.approx(14.5)


def test_every_new_metric_has_an_entry_and_a_reader():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), "..",
                           "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    counters = ("runtime_init_s", "step_trace_lower_s", "step_compile_s",
                "compile_cache_misses")
    for name in (*READERS, *counters):
        assert name in entries
        importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert all(entries[n]["source"] == "program_counter" and
               "workloads" not in entries[n] for n in counters)


def test_scope_of_and_describe():
    assert sr.scope_of(
        "jit(step)/shard_map/transpose(jvp(layer0))/jvp(layer0)/checkpoint/"
        "rematted_computation/attn/dot_general") == ["layer0", "attn"]
    assert sr.scope_of("jit(step)/jvp(ResNet)/stage1/Block_3/Conv_0/"
                       "conv_general_dilated") == ["ResNet", "stage1",
                                                   "Block_3", "Conv_0"]
    assert sr.scope_of("jit(step)/shard_map/add") == []
    text = sr.describe(PB)
    assert "0.015000  forward" in text                  # the whole file
    assert "0.005000  backward       layer0/attn" in text
    assert "0.006000  fusion  'jit(step)/shard_map/add'" in text


def test_counters_through_hvd_metrics():
    """The counters' readers on a live program (CPU): a jitted step's
    seconds by its function's name, and None from a program without the
    families."""
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from benchmarks import program_counters as pc

    hvd.init()
    try:
        def _train_step(x):
            return hvd.allreduce(x.sum())

        step = hvd.run_step(_train_step, in_specs=hvd.batch_spec(0),
                            out_specs=hvd.REPLICATED)
        step(hvd.shard_batch(jnp.ones((hvd.size() * 2, 3))))
        ctx = types.SimpleNamespace(job=types.SimpleNamespace(step=step))
        for metric in ("step_trace_lower_s", "step_compile_s",
                       "compile_cache_misses", "runtime_init_s"):
            read = importlib.import_module(
                f"benchmarks.layer_metrics.{metric}").read
            assert read(ctx) is not None and read(ctx) >= 0
        assert pc.step_seconds(ctx, "trace") > 0
        assert pc.value("hvdtpu_spmd_no_such_family") is None
        other = types.SimpleNamespace(job=types.SimpleNamespace(
            step=lambda: None))
        assert pc.step_seconds(other, "trace") is None
    finally:
        hvd.shutdown()
