"""The expert layer's readers (``layer_metrics/moe_*.py``) against
``data/moe_trace.textproto``, whose operations, names and expected sums are
written out in the file; ``flops_moe.py`` against a hand count; and the
``olmoe-1b-7b_s4096`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks import flops, flops_moe
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = ("moe_ms", "moe_experts_ms", "moe_route_ms",
       "moe_experts_roofline_pct", "moe_load_max_over_mean")


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "moe_trace.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("moe") / "moe_trace.xplane.pb"
    path.write_bytes(built)
    return str(path)


def ctx_of(trace, **job):
    costs = {"grouped_matmul": {"match": "^ragged-dot-", "ops": 3.5e9,
                                "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs, **job), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


@pytest.mark.parametrize("metric, want", [
    ("moe_ms", 10.0), ("moe_experts_ms", 7.0), ("moe_route_ms", 3.0)])
def test_scope_readers(metric, want, trace_file, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    trace = tr.read_xplane(trace_file, SPANS_NS)
    assert reader(metric)(ctx_of(trace)) == pytest.approx(want)


def test_roofline_share_is_least_time_over_the_scope(trace_file,
                                                     monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    ctx = ctx_of(tr.read_xplane(trace_file, SPANS_NS))
    # 3.5e9 operations at 1e12 a second: 3.5 ms of the scope's 7.
    assert reader("moe_experts_roofline_pct")(ctx) == pytest.approx(50.0)
    # A job that names no grouped matmul: the scope alone, and no share.
    ctx.job.kernel_costs.clear()
    assert reader("moe_experts_ms")(ctx) == pytest.approx(1.5)
    assert reader("moe_experts_roofline_pct")(ctx) is None


def test_readers_return_nothing_where_the_program_has_no_expert_layer(
        monkeypatch):
    """A dense program's trace, a rehearsal's (no device plane), a job that
    kept no counts: None, never an error."""
    dense = os.path.join(HERE, "data", "scoped_trace.xplane.pb")
    monkeypatch.setattr(sr, "newest_xplane", lambda: dense)
    with_device = ctx_of(tr.read_xplane(dense, SPANS_NS))
    without = ctx_of(tr.Trace({}, {}))
    for metric in NEW:
        assert reader(metric)(with_device) is None
        assert reader(metric)(without) is None


def test_load_is_the_busiest_expert_over_the_mean():
    ctx = ctx_of(None, expert_counts=np.array([[4, 4, 4, 4], [8, 2, 2, 4]]))
    assert reader("moe_load_max_over_mean")(ctx) == pytest.approx(2.0)


def test_flops_by_hand():
    # One token of OLMoE's layer: router 2*2048*64; 8 experts of three
    # 2048x1024 matrices, two operations a multiply-accumulate.
    assert flops_moe.expert_layer_forward_flops(2048, 64, 1024, 8) \
        == 262_144 + 8 * 3 * 4_194_304 == 100_925_440
    # A token trained at S=4096, one layer: q, k, v, o projections
    # 4 * 2 * 2048 * 2048, attention over (4096 + 1) / 2 keys at 4 * 2048
    # a key, the expert layer, the 2048 x 50304 head; times three.
    attention = 4 * 2 * 2048 * 2048 + 4097 * 2 * 2048
    head = 2 * 2048 * 50304
    assert flops_moe.moe_train_flops(
        4096, 1, 2048, 16, 16, 128, experts=64, width=1024, top_k=8,
        vocab=50304) == 3 * (attention + 100_925_440 + head)
    # Two layers count the blocks twice and the head once.
    assert flops_moe.moe_train_flops(
        4096, 2, 2048, 16, 16, 128, experts=64, width=1024, top_k=8,
        vocab=50304) == 3 * (2 * (attention + 100_925_440) + head)
    # One pass of the cell's grouped matmuls: 65,536 rows through three
    # 2048x1024 matrices; 64 experts' matrices and the rows in and out, bf16.
    cost = flops_moe.grouped_matmul_pass_cost(65536, 2048, 1024, 64)
    assert cost == {"ops": 3 * 2 * 65536 * 2048 * 1024,
                    "bytes": 2 * (3 * 64 * 2048 * 1024 + 2 * 65536 * 2048)}
    assert cost["ops"] == 824_633_720_832
    seconds, bound = flops.roofline_seconds(
        cost, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and seconds == pytest.approx(4.186e-3, rel=1e-3)


def test_the_cell_in_rehearsal_reads_its_new_metrics():
    """The control flow of ``--workload olmoe-1b-7b_s4096 --trace 1`` at the
    twin's tiny sizes on 4 CPU devices: the check's five rows pass, and of
    the new metrics the one that needs no device trace is read (a CPU run
    has no device plane: the four trace readers are held to the fixture
    above)."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "olmoe-1b-7b_s4096", "--seed", "2147483999",
         "--seconds", "1", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    checks = [ln for ln in lines if "check: " in ln]
    assert len(checks) == 5 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "load-balance term", "router z term",
                 "gradient norm after the exchange", "update norm"):
        assert any(f"check: {what}:" in ln for ln in checks)
    read = next(ln for ln in lines if "metrics read" in ln).split()
    assert "moe_load_max_over_mean" in read and "tok_mfu_pct" in read


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["layer"] == "Expert layer"
        assert entries[name]["moves"] == "tok_s_chip"
        assert entries[name]["workloads"][0] == "olmoe-1b-7b_s4096"
        reader(name)
