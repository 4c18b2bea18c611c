"""The early router's reader (``layer_metrics/router_early_ms.py``)
against ``data/prerouted_trace.textproto``, whose operations, names and
expected sums are written out in the file, and the
``smallthinker-21b-a3b_s16384`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "smallthinker-21b-a3b_s16384"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """``built(name)``: ``data/<name>.textproto`` as an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    def build(name):
        with open(os.path.join(HERE, "data", name + ".textproto")) as f:
            space = ProfileData.text_proto_to_serialized_xspace(f.read())
        path = tmp_path_factory.mktemp(name) / (name + ".xplane.pb")
        path.write_bytes(space)
        return str(path)

    return build


def ctx_of(trace):
    costs = {"grouped_matmul": {"match": "^ragged-dot-", "ops": 1e9,
                                "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def test_the_early_product_is_read_in_all_three_passes(built, monkeypatch):
    path = built("prerouted_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    # Forward 1, recomputed 3, backward 4, and the 3 ms of layer1's forward
    # product that lie inside the window, over 2 steps.
    assert reader("router_early_ms")(ctx) == pytest.approx(5.5)
    # The accepted readers see the same file: the early product is the
    # expert layer's time and not the ``router`` scope's, nor the kernels'.
    assert reader("moe_route_ms")(ctx) == pytest.approx(2.0)
    assert reader("moe_ms")(ctx) == pytest.approx(9.5)
    assert reader("moe_experts_ms")(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ["moe_trace", "window_trace", "cca_trace"])
def test_a_program_without_the_scope_reads_nothing(built, monkeypatch, name):
    """The parent's programs (routers that read what the experts read, under
    ``moe/router``), and a rehearsal's trace (no device plane): None, never
    an error."""
    path = built(name)
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    assert reader("router_early_ms")(
        ctx_of(tr.read_xplane(path, SPANS_NS))) is None
    assert reader("router_early_ms")(ctx_of(tr.Trace({}, {}))) is None


def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload smallthinker-21b-a3b_s16384 --trace
    1`` at the twin's tiny sizes on 4 CPU devices: the check's six rows
    pass, and of the cell's metrics every one that needs no device trace is
    read."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    checks = [ln for ln in lines if "check: " in ln]
    assert len(checks) == 6 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "gradient norm after the exchange", "update norm",
                 "token-expert choices shared with the reference",
                 "window layers' key and value gradients along",
                 "routers' outputs off the reference's on the same"):
        assert any(f"check: {what}" in ln for ln in checks), what
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert "router_early_ms" in listed and "flash_dq_ms" not in listed
    traced = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    # The window's drift needs three segments, which a loaded CPU may not
    # make of one second.
    assert set(read.split()) | {"tok_window_drift_pct"} == listed - traced
